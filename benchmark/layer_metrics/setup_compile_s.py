"""entry: seconds the backend spent compiling, or loading from the persistent
cache, over set-up (`telemetry/mfu.RecompileCounter.seconds`)."""


def read(run):
    return run["compile"]["setup"]["compile_seconds"]
