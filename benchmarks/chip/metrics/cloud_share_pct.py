"""Policy (`core/`): the share of the window's admissions that ran on the
cloud replica. Admissions are counted per engine step from the engine's
own counters; an engine's model comes from `replica_model`, and the cloud
is the replica of the widest model. None for a fleet of one model.

A diagnostic of the routing, not a goal: PerLLM trades the cloud's
latency and energy against the edges' capacity, so a larger share is
neither better nor worse in itself, and a routing change is judged by
`deadline_met_pct`. `BENCHMARK.json` asks every metric for a direction;
"higher" was picked only because the share grows as the policy uses the
cloud more, and the metric has no bound."""


def read(rec):
    models = rec.replica_model
    if len(set(models)) < 2:
        return None
    cloud = max(set(models), key=lambda name: rec.shapes[name].d_model)
    steps = rec.in_window(rec.engine_steps)
    total = sum(s.admitted for s in steps)
    if not total:
        return None
    mine = sum(s.admitted for s in steps if models[s.engine] == cloud)
    return 100.0 * mine / total
