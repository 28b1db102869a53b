"""The whole step's share of the chip's bf16 peak: the target's forward
FLOPs per committed token (weights and attention over the token's context;
drafts and rejected positions do not count) times the committed tokens per
second of the traced window, over the peak."""


def read(run):
    if run.wall_s <= 0 or not run.committed_tokens or \
            run.peak_flops != run.peak_flops:   # no peak known (NaN)
        return None
    rate = run.committed_tokens / run.wall_s
    return 100.0 * rate * run.target_flops_per_token / run.peak_flops
