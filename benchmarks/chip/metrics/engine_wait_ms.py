"""Engine admission (`serving/engine.py`): mean of the engine's own
stamps `first_token_at - submitted_at` over the window's requests that
got a first token: queue wait plus prefill."""


def read(rec):
    w = rec.engine_waits
    return 1e3 * sum(w) / len(w) if w else None
