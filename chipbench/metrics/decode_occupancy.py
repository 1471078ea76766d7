"""decode_occupancy (scheduler, ``serve/engine.py`` ``_serve_loop_packed``):
share of the decode batch's row-steps in the traced call that served a
live request: ``decode_row_steps`` over ``max_batch`` x ``decode_steps``
(``serve_stats``). Every decode chunk steps all ``max_batch`` rows; the
rest are rows past their EOS or budget inside a chunk
(``decode_surplus_row_steps``), and rows that are free or still
prefilling. Read from a trace that holds the device's ops only: a call
traced without the chip (a CPU rehearsal) reads None."""


def read(run):
    steps = run.stats.get("decode_steps")
    if not steps or not run.reading.ops:
        return None
    rows = run.mix["engine"]["max_batch"]
    used = run.stats["decode_row_steps"]
    surplus = run.stats["decode_surplus_row_steps"]
    print(f"decode_occupancy: {steps} decode steps of {rows} rows; "
          f"{used} row-steps of live rows, {surplus} past EOS or budget, "
          f"{rows * steps - used - surplus} of free or prefilling rows",
          flush=True)
    return 100.0 * used / (rows * steps)
