"""model, a chip's share of the expert layer: the share of the router's
assignments that land on experts this chip holds, in %, from
`ops/moe.router_stats` on the scoring forward of set-up's greedy check (the
driver's `run["moe"]`): held / routed width for a uniform router, 6.25 % at
12 of 192. The rest is what the absent chips would compute."""


def read(run):
    frac = (run.get("moe") or {}).get("moe/routed_here_frac")
    return None if frac is None else 100.0 * frac
