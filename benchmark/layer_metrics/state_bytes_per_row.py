"""serving: `serving/state_bytes_per_row` of `engine.metrics()`: what the
session keeps a row that is not a page, over every conv layer (the last
K - 1 values of `g` a layer: 65,536 B for 8 layers of 2 x 2,048 in bf16).
Nothing where the program exports no such counter or keeps no state."""


def read(run):
    end = (run.get("counters") or {}).get("end", {})
    return end.get("serving/state_bytes_per_row") or None
