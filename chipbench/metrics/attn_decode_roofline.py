"""attn_decode_roofline (kernels, ``kernels/attn`` paged decode): the
least time the decode steps' attention needs, over the device time of
the paged decode kernel's ops.

The work counts real keys only: each decode step of a live request reads
the K and V of the prompt and of the tokens served so far (capped by the
sliding window) in every layer, and does QK and PV against them
(``work.decode_attention_work``); rows that are idle or finished count
nothing. The ops are the custom calls whose operands open with the block
table and the per-row lengths and starts (``shapes.paged_decode``)."""
from chipbench import shapes, work


def read(run):
    found = [o for o in run.reading.ops if shapes.paged_decode(o.name)]
    if not found:
        return None
    ops = nbytes = 0.0
    for p, s in zip(run.prompt_lens, run.served_lens):
        a, b = work.decode_attention_work(run.cfg, p, s)
        ops, nbytes = ops + a, nbytes + b
    seconds = sum(o.dur_ns for o in found) / 1e9
    value, bound = work.roofline_share(ops, nbytes, seconds, run.peaks)
    print(f"attn_decode_roofline: {len(found)} ops, {seconds!r} s on the "
          f"device, {ops!r} ops, {nbytes!r} bytes, {bound}-bound",
          flush=True)
    return value
