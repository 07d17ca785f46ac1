"""Model step at the cloud's widths: mean wall time of the window's
`ServingEngine.step` calls on the cloud replica (the replica of the
widest model) that admitted nothing and had lanes to decode. None for a
fleet of one model."""


def read(rec):
    models = rec.replica_model
    if len(set(models)) < 2:
        return None
    cloud = max(set(models), key=lambda name: rec.shapes[name].d_model)
    steps = [s for s in rec.in_window(rec.engine_steps)
             if not s.admitted and s.ctx_lens and models[s.engine] == cloud]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
