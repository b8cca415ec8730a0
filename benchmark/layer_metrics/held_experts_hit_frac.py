"""model, a chip's share of the expert layer: of the experts this chip holds,
those a decode step's live rows reach, a layer, in % (`moe/held_experts_hit`
of the driver's `run["moe"]`: `serving/held_experts_hit`, counted on the
device from the router's choices, over `serving/decode_steps` and the expert
layers, in the window; over the configuration's `num_experts_held`). What
sets the step's bytes: each reached expert is three kernels read once.
Nothing where the program has no such counter or the file no share."""


def read(run):
    hit = (run.get("moe") or {}).get("moe/held_experts_hit")
    held = run.get("config", {}).get("num_experts_held")
    if hit is None or not held:
        return None
    return 100.0 * hit / held
