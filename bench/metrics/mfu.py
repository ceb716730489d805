"""mfu (%): model FLOPs of the local steps per round, times the rounds of
the window, over the window and the chips' bf16 peak.

The FLOPs are the algorithm's, from the cell's shapes: the configuration's
plain model (``bench/models/<model_type>.py``) counts one training step on
one sequence, and a round makes ``local_steps`` of them on each worker's
``batch_per_worker`` sequences.  Recomputation (remat), the penalty
gradient and the round are not model FLOPs.
"""


def flops_per_round(model, config, traffic) -> float:
    seqs = traffic["workers"] * traffic["local_steps"] \
        * traffic["batch_per_worker"]
    return seqs * model.train_flops(config, traffic["seq_len"])


def read(ctx):
    if not ctx.rounds:
        return None
    flops = ctx.rounds * flops_per_round(ctx.model, ctx.config, ctx.traffic)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / ctx.window_s / peak
