"""entry: programs new to the process inside the window
(`RecompileCounter.count` delta). Expected 0; `correct` is false otherwise."""


def read(run):
    return run["compile"]["window"]["compiles"]
