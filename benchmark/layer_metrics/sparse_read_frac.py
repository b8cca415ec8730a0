"""cache: of the slots the selecting rows' decode steps held
(`serving/sparse_slots_held` of `engine.metrics()`: a live row past
`dense_len`, summed over steps), the share the selection LET a sparse layer read of them
(`serving/sparse_slots_read`: `topk x block_size` at most, docs/SALA.md;
the host's arithmetic over the rows' lengths, not a count of what the
device fetched: `sala_sparse_read_roofline` reads that from the trace),
end less start, in %: near 4,096 over the mean slots such a row holds.
Nothing where the program exports no such counters or no row selected."""


def read(run):
    c = run.get("counters") or {}
    start, end = c.get("start") or {}, c.get("end") or {}
    try:
        read_ = end["serving/sparse_slots_read"] - start["serving/sparse_slots_read"]
        held = end["serving/sparse_slots_held"] - start["serving/sparse_slots_held"]
    except KeyError:
        return None
    return 100.0 * read_ / held if held else None
