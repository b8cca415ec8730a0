"""model: of the rows a sparse layer's decode selection COULD run over (the
resident rows of every step, `serving/select_rows_resident`, docs/SALA.md),
the share it ran over (`serving/select_rows_run`: the rows that select, a
trip of `core/sala.select_needed`'s loop each), end less start, in %. A
program that gathers, scores and ranks every resident row's compressed keys
would read 100. Nothing where the program exports no such counters or a
sparse layer took no step."""


def read(run):
    c = run.get("counters") or {}
    start, end = c.get("start") or {}, c.get("end") or {}
    try:
        ran = end["serving/select_rows_run"] - start["serving/select_rows_run"]
        resident = (end["serving/select_rows_resident"]
                    - start["serving/select_rows_resident"])
    except KeyError:
        return None
    return 100.0 * ran / resident if resident else None
