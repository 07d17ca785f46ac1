"""Fleet runtime and policy (`serving/perllm_server.py`, `core/`): host
time of `PerLLMServer.step` outside the `ServingEngine.step` it ran,
over the window, per request routed. Benchmark timers."""


def read(rec):
    steps = rec.in_window(rec.server_steps)
    if not steps or not rec.routed:
        return None
    own = sum(s.t1 - s.t0 - s.engine_s for s in steps)
    return 1e6 * own / rec.routed
