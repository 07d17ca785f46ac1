"""Whole decode step: model FLOPs of the window's decode-only engine
steps (2 x matmul parameters per active lane, plus attention over each
lane's positions) over their wall time times one chip's bf16 peak."""
from chipbench import flops


def read(rec):
    steps = [s for s in rec.in_window(rec.engine_steps)
             if not s.admitted and s.ctx_lens]
    peak = rec.peaks.get("bf16_flops")
    if not steps or not peak:
        return None
    fl = sum(flops.decode_step_flops(
        rec.shapes[rec.replica_model[s.engine]], s.ctx_lens) for s in steps)
    wall = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * fl / (wall * peak)
