"""device, across chips: self time of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operations over device busy
time, from the reduced trace (harness/xplane.py), in %."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
