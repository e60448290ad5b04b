"""Trainer step: model FLOP/s utilisation = ``train_tokens_per_s`` x
operations per token / (chips x the chip's bf16 peak). Operations per
token are ``stats.train_flops_per_token`` over the parameters that are
matrices of a matmul (the embedding table is a gather and is left out);
recomputation is not counted."""
from benchmark import stats
from benchmark.layer_metrics import train_tokens_per_s


def read(ctx):
    t = ctx.get("train")
    tokens_per_s = train_tokens_per_s.read(ctx)
    if not t or tokens_per_s is None:
        return None
    per_token = stats.train_flops_per_token(
        t["n_params"] - t["n_embedding_params"], t["num_layers"], t["seq"], t["hidden_size"]
    )
    peak = t["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * tokens_per_s * per_token / peak
