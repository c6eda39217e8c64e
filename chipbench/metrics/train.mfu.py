"""Whole-step model FLOP utilization: model operations per token (6 per
matmul parameter plus attention; remat recompute not counted) times the
window's ``train_tokens_per_s``, over the chip's bf16 peak."""

from chipbench import costs


def read(rec):
    rate = rec["e2e"].get("train_tokens_per_s")
    if not rate:
        return None
    flops = costs.train_flops_per_token(rec["model"], rec["record"]["seq"])
    return 100.0 * flops * rate / rec["peaks"]["bf16_flops"]
