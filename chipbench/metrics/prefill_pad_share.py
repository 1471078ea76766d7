"""prefill_pad_share (scheduler, ``serve/engine.py`` ``_serve_loop_packed``):
share of the tokens the traced call's prefill steps ran that were padding,
from the engine's own counts: 1 - prompt_tokens / packed_prefill_tokens."""


def read(run):
    padded = run.stats.get("packed_prefill_tokens", 0)
    if not padded:
        return None
    return 100.0 * (1.0 - run.stats["prompt_tokens"] / padded)
