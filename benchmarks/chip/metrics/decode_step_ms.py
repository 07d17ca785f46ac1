"""Engine decode (`serving/engine.py`): mean wall time of the window's
`ServingEngine.step` calls that admitted nothing and had lanes to
decode. Admission is seen from the engine's prefill and prefix-hit
counters."""


def read(rec):
    steps = [s for s in rec.in_window(rec.engine_steps)
             if not s.admitted and s.ctx_lens]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
