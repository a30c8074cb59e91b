"""The benchmark's own count of forward and backward operations per token
(``benchmark/peaks.py``: recomputation not counted, causal attention counted
once) times the traced run's ``train_tok_s``, over chips times the bf16 peak."""

from benchmark import peaks


def read(ctx):
    rate = ctx["e2e"].get("train_tok_s")
    if rate is None:
        return None
    flops = peaks.train_flops_per_token(ctx["config"], ctx["traffic"]["seq_len"])
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate * flops / (ctx["chips"] * peak)
