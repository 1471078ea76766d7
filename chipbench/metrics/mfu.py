"""mfu (step functions, the whole step): the model operations the traced
call required, over its wall seconds, over the chip's bf16 peak.

Required operations (``work.request_flops``): every layer over each prompt
token and each served token but the last, DBB projections at their
non-zero multiply-adds, attention over each token's real context (capped
by the sliding window), the head dense once per served token."""
from chipbench import work


def read(run):
    ops = sum(work.request_flops(run.cfg, p, s)
              for p, s in zip(run.prompt_lens, run.served_lens))
    if ops <= 0 or run.window_s <= 0:
        return None
    return 100.0 * ops / run.window_s / run.peaks["bf16_flops"]
