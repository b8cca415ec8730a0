"""scheduler, generation by blocks: of the live row-forwards of the window,
those that were COMMITS (a block's last forward, which unmasks nothing and
writes its K/V for good), `serving/commit_forwards` over
`serving/block_forwards`, in %: what fusing a commit with the next block's
first denoise forward would take away (docs/BLOCKDIFF.md "what is left").
Nothing where the program has no such counters."""


def read(run):
    block = run.get("block") or {}
    forwards = block.get("serving/block_forwards")
    if not forwards:
        return None
    return 100.0 * block.get("serving/commit_forwards", 0) / forwards
