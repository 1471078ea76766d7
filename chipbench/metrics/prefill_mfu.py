"""prefill_mfu (step functions, the prefill steps): the operations every
prompt of the traced call required (``work.prefill_flops``), over the
device time of the prefill step programs, over the chip's bf16 peak.

The prefill steps are the device programs of the engine's jitted ``step``
functions (packed and continuation prefill); decode runs inside its
``chunk`` program and is not counted here."""
from chipbench import work

PROGRAM = "jit_step("


def read(run):
    ns = sum(m.dur_ns for m in run.reading.modules
             if m.name.startswith(PROGRAM))
    if ns <= 0:
        return None
    ops = sum(work.prefill_flops(run.cfg, p)
              for p, s in zip(run.prompt_lens, run.served_lens) if s > 0)
    return 100.0 * ops / (ns / 1e9) / run.peaks["bf16_flops"]
