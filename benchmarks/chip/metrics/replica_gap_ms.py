"""Fleet runtime (`serving/perllm_server.py`): mean host time between one
replica's consecutive `ServingEngine.step` calls while its lanes were
occupied (the later step found lanes active), over the window's steps of
every replica. The server steps its engines one after another from one
loop, so on a fleet this is mostly the other replicas' steps. Each engine
counts the same gaps (`engine.step_gap_ns`, `engine.step_gaps`)."""


def read(rec):
    lo, hi = rec.window
    last, gaps = {}, []
    for s in rec.engine_steps:          # in the order they ran
        prev = last.get(s.engine)
        if prev is not None and s.ctx_lens and lo <= s.t0 < hi:
            gaps.append(s.t0 - prev.t1)
        last[s.engine] = s
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
