"""scheduler, generation by blocks: tokens unmasked a live row-forward over
the window, `serving/tokens_unmasked` over `serving/block_forwards` (both
counted on the device, a row a forward: docs/BLOCKDIFF.md). A block of B
tokens costs its denoise forwards and one commit: 4 tokens in 5 forwards at 4
steps (0.8), in 3 at 2 (1.33). Nothing where the program has no such
counters (every autoregressive model, a parent commit)."""


def read(run):
    block = run.get("block") or {}
    forwards = block.get("serving/block_forwards")
    if not forwards:
        return None
    return block.get("serving/tokens_unmasked", 0) / forwards
