"""model: `serving/loop_passes_per_token` of `engine.metrics()`: the times
the session's forwards pass a token through the layer stack (docs/OURO.md:
4 for Ouro-2.6B, whose exit threshold of 1 no gate reaches). The guard
against a later change that leaves a pass out. Nothing where the program
exports no such counter (the parent of the PR that wrote it)."""


def read(run):
    end = (run.get("counters") or {}).get("end") or {}
    return end.get("serving/loop_passes_per_token")
