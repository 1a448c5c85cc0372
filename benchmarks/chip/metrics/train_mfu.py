"""Model FLOP/s utilization of the training step, in percent of the
chip's bf16 peak: model FLOPs per token (6 x matmul parameters including
the head, excluding the embedding lookup, plus causal attention;
recomputation not counted; ``flops.train_flops_per_token``) times the
traced window's tokens per second."""


def read(rec):
    host = rec["host"]
    if host.get("kind") != "train":
        return None
    return 100.0 * host["flops_per_token"] * host["tokens_per_s"] / rec[
        "peaks"]["bf16_flops_per_s"]
