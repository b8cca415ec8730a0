"""serving: of the window's admission forwards of a model that keeps a state
(`serving/state_resets` + `serving/state_piece_carries` of `engine.metrics()`,
end less start), the share that took over the state its last piece left
(`serving/state_piece_carries`: a prompt of n prefill pieces counts n - 1), in %:
how much of the prefill work hands both state leaves from piece to piece.
Nothing where the program exports no such counters or made no such forward."""


def read(run):
    c = run.get("counters") or {}
    start, end = c.get("start") or {}, c.get("end") or {}
    try:
        carries = end["serving/state_piece_carries"] - start["serving/state_piece_carries"]
        resets = end["serving/state_resets"] - start["serving/state_resets"]
    except KeyError:
        return None
    return 100.0 * carries / (carries + resets) if carries + resets else None
