"""Device mesh + sharding rules — the TPU replacement for the reference's
entire memory/distribution story.

The reference scales by CPU↔GPU offload choreography and an unused
accelerate/NCCL scaffold (SURVEY.md §2.3, `/root/reference/GRPO/
grpo_trainer.py:168-172,475-476,622-626`). Here the same capability is a
`jax.sharding.Mesh` with axes:

- `data`  — batch/data parallel (primary scaling axis; DCN axis multi-slice)
- `fsdp`  — parameter/optimizer-state sharding (ZeRO-equivalent; replaces the
            optimizer-state CPU paging entirely)
- `tensor`— megatron-style tensor parallel for >8B models
- `sp`    — sequence/context parallel (ring attention over ICI;
            `parallel/sp.py`). Params and batch are replicated over sp; the
            sequence dim of the scoring/update passes shards over it.

All rules are GSPMD PartitionSpecs over the *stacked* param tree of
core/model.py; XLA inserts the collectives (psum/all-gather over ICI).
Batch axes shard over (data, fsdp) jointly — fsdp acts as a second data axis
for activations, param all-gathers ride the fsdp axis.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    data: int = -1      # -1 = all remaining devices
    fsdp: int = 1
    tensor: int = 1
    sp: int = 1         # sequence-parallel extent (ring attention)
    # number of TPU slices the DATA axis spans (multi-slice / DCN scaling).
    # The data axis becomes (dcn_data × per-slice data) with slices
    # slowest-varying, so fsdp/tensor/sp collectives stay inside a slice
    # (ICI) and only the once-per-update gradient psum crosses DCN — the
    # layout §5.8 calls for. 1 = single slice (no DCN traffic at all).
    dcn_data: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int, int]:
        d, f, t, s = self.data, self.fsdp, self.tensor, self.sp
        dcn = max(self.dcn_data, 1)
        known = (f if f > 0 else 1) * (t if t > 0 else 1) * (s if s > 0 else 1)
        if d == -1:
            d = n_devices // (known * dcn) * dcn
        if d * f * t * s != n_devices:
            raise ValueError(
                f"mesh {d}x{f}x{t}x{s} != {n_devices} devices"
            )
        if d % dcn != 0:
            raise ValueError(f"data axis {d} not divisible by dcn_data {dcn}")
        return d, f, t, s


def _slice_ordered(devices, dcn: int):
    """Order devices slice-major so reshaping puts whole slices on the
    leading (DCN) part of the data axis. TPU runtimes expose `slice_index`
    on each device — when present, the physical layout must actually match
    `dcn` (distinct slices == dcn, equal sizes), else fsdp/tensor/sp
    collectives would silently straddle slice boundaries and cross DCN
    every layer. Hosts without `slice_index` (CPU test meshes) fall back to
    id order, which partitions the virtual devices into `dcn` contiguous
    groups — same axis semantics, no physical slices to respect."""
    if all(hasattr(d, "slice_index") for d in devices):
        slices = sorted({d.slice_index for d in devices})
        if len(slices) != dcn:
            raise ValueError(
                f"dcn_data={dcn} but devices span {len(slices)} slices"
            )
        per = [sum(d.slice_index == s for d in devices) for s in slices]
        if len(set(per)) != 1:
            raise ValueError(f"uneven devices per slice: {per}")
        return sorted(devices, key=lambda dev: (dev.slice_index, dev.id))
    return sorted(devices, key=lambda dev: dev.id)


def make_mesh(config: MeshConfig = MeshConfig(), devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    d, f, t, s = config.resolve(len(devices))
    dcn = max(config.dcn_data, 1)
    if dcn > 1:
        devices = _slice_ordered(devices, dcn)
    arr = np.asarray(devices).reshape(d, f, t, s)
    return Mesh(arr, ("data", "fsdp", "tensor", "sp"))


# ---------------------------------------------------------------------------
# Sharding rules for the stacked Qwen2 tree (+ optional LoRA subtree)
# ---------------------------------------------------------------------------

# leaf-name path suffix -> PartitionSpec (leading None = stacked layer axis)
_RULES = {
    ("embed_tokens",): P("tensor", "fsdp"),
    ("norm",): P(None),
    ("lm_head",): P("fsdp", "tensor"),
    # attention: out-features sharded by tensor, in-features by fsdp
    ("layers", "q_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("layers", "k_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("layers", "v_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("layers", "q_proj", "bias"): P(None, "tensor"),
    ("layers", "k_proj", "bias"): P(None, "tensor"),
    ("layers", "v_proj", "bias"): P(None, "tensor"),
    ("layers", "o_proj", "kernel"): P(None, "tensor", "fsdp"),
    # mlp: intermediate dim by tensor
    ("layers", "gate_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("layers", "up_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("layers", "down_proj", "kernel"): P(None, "tensor", "fsdp"),
    # sparse experts [L, E, in, out] (ops/moe.py): the expert axis by tensor,
    # the contracted width by fsdp; the router is small and every token reads
    # all of it, so it is replicated (as are OLMoE's q_norm / k_norm, which
    # fall through to the default). No expert-parallel axis and no
    # all-to-all yet: ROADMAP R3 brings the four-chip expert cell
    ("layers", "experts", "gate_proj", "kernel"): P(None, "tensor", "fsdp", None),
    ("layers", "experts", "up_proj", "kernel"): P(None, "tensor", "fsdp", None),
    ("layers", "experts", "down_proj", "kernel"): P(None, "tensor", "fsdp", None),
    ("layers", "router", "kernel"): P(None, None, None),
    # A.X-K1 (core/mla.py): the shared expert like a dense MLP; MLA's
    # bottlenecks keep their small latent widths whole (q_lora_rank,
    # kv_lora_rank + rope: every head reads all of them) and shard the
    # per-head side by tensor, as q/k/v and o do. The latent CACHE is one
    # head wide and stays unsharded on its head axis.
    ("shared_expert", "gate_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("shared_expert", "up_proj", "kernel"): P(None, "fsdp", "tensor"),
    ("shared_expert", "down_proj", "kernel"): P(None, "tensor", "fsdp"),
    ("layers", "q_a_proj", "kernel"): P(None, "fsdp", None),
    ("layers", "q_b_proj", "kernel"): P(None, None, "tensor"),
    ("layers", "kv_a_proj", "kernel"): P(None, "fsdp", None),
    ("layers", "kv_b_proj", "kernel"): P(None, None, "tensor"),
    ("layers", "input_layernorm"): P(None, None),
    ("layers", "post_attention_layernorm"): P(None, None),
    # LoRA: A shards like the input dim, B like the output dim
    ("a",): P(None, "fsdp", None),
    ("b",): P(None, None, "tensor"),
}

_RULES_BY_LEN = sorted(_RULES.items(), key=lambda kv: -len(kv[0]))


def _spec_for_path(path: tuple[str, ...]) -> P:
    # int8 rollout kernels (core/quant.py): kernel_q shards exactly like the
    # kernel it replaces; its per-output-channel scale [L, 1, out] keeps the
    # kernel's out-axis sharding with the contracted axis unsharded
    if path and path[-1] == "kernel_q":
        path = path[:-1] + ("kernel",)
    elif path and path[-1] == "kernel_scale":
        kspec = _spec_for_path(path[:-1] + ("kernel",))
        return P(*(list(kspec)[:-2] + [None, list(kspec)[-1]])) \
            if len(kspec) >= 2 else kspec
    for suffix, spec in _RULES_BY_LEN:
        if path[-len(suffix):] == suffix:
            return spec
    return P()  # replicate anything unmatched


def param_sharding_rules(params) -> dict:
    """PartitionSpec pytree matching `params` (works for LoRA subtrees too)."""

    def spec(path, leaf):
        keys = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        # strip a leading "lora" namespace so LoRA trees reuse layer rules
        if keys and keys[0] == "lora":
            keys = keys[1:]
        # A.X-K1's leading dense stack holds the same leaves as `layers`
        if keys and keys[0] == "dense_layers":
            keys = ("layers",) + keys[1:]
        return _spec_for_path(keys)

    return jax.tree_util.tree_map_with_path(spec, params)


def shard_params(params, mesh: Mesh, rules=None):
    """Place a param tree on the mesh according to the rules (host → device)."""
    rules = rules if rules is not None else param_sharding_rules(params)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params,
        rules,
    )


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the batch dim over (data, fsdp); replicate other dims."""
    return NamedSharding(mesh, P(("data", "fsdp"), *([None] * (ndim - 1))))


def split_worker_groups(devices, n_workers: int):
    """Partition a rollout device group into `n_workers` equal per-worker
    sub-groups (the fleet's per-worker generation meshes,
    `RLConfig.rollout_workers > 1` + `rollout_devices > 0`).

    Groups are contiguous in id order. Device ids are slice-major on TPU
    pods (slice 0's chips number before slice 1's), so when the per-worker
    size divides the slice size each worker's collectives stay inside a
    slice (ICI); a per-worker group that straddles a slice boundary is
    warned — its own collectives would ride DCN every decode step. Workers
    joining beyond the initial cohort reuse the groups round-robin (worker
    id mod n_groups), so elastic membership never re-partitions silicon
    mid-run."""
    if n_workers < 1:
        raise ValueError(f"n_workers={n_workers} must be >= 1")
    if len(devices) % n_workers != 0:
        raise ValueError(
            f"rollout_devices={len(devices)} not divisible by "
            f"rollout_workers={n_workers} — every worker needs an "
            "identically-shaped generation mesh (one compiled executable "
            "serves the whole fleet)"
        )
    per = len(devices) // n_workers
    ordered = sorted(devices, key=lambda d: d.id)
    groups = [ordered[i * per:(i + 1) * per] for i in range(n_workers)]
    if all(hasattr(d, "slice_index") for d in devices):
        import warnings

        for i, g in enumerate(groups):
            slices = {d.slice_index for d in g}
            if len(slices) > 1:
                warnings.warn(
                    f"split_worker_groups: worker {i}'s device group spans "
                    f"slices {sorted(slices)} — its generation collectives "
                    "ride DCN every decode step. Pick rollout_workers so "
                    "the per-worker size divides the slice size.",
                    RuntimeWarning,
                    stacklevel=2,
                )
    return groups


def split_rollout_devices(devices, k: int):
    """(train_devices, rollout_devices): reserve `k` devices for generation.

    The disaggregated-rollout layout (trainer `rollout_devices`): training
    runs on one device group, generation on another, with one param sync
    per update crossing between them. On a multi-slice pod the reservation
    prefers WHOLE slices (highest slice_index first) so the rollout mesh's
    own collectives stay on ICI and only the param sync rides DCN; when no
    suffix of whole slices sums to `k` (or on hosts without slice_index,
    e.g. CPU test meshes) it falls back to the id-ordered tail — which on a
    MULTI-slice pod either spreads the rollout mesh over several slices
    (rollout collectives then ride DCN every decode step) or carves the
    rollout group out of one slice shared with training (train-mesh
    collectives straddle the cut); both are warned (ADVICE r5).
    Single-slice hosts warn about nothing — every link is ICI. The
    whole-slice reservation assumes HOMOGENEOUS slices (equal device
    counts per slice, the normal TPU pod shape); pick `k` as a multiple of
    the slice size to stay on the whole-slice path."""
    if not 0 < k < len(devices):
        raise ValueError(
            f"rollout_devices={k} must leave >=1 of {len(devices)} devices "
            "for training"
        )
    if all(hasattr(d, "slice_index") for d in devices):
        by_slice = {}
        for d in devices:
            by_slice.setdefault(d.slice_index, []).append(d)
        picked = []
        for s in sorted(by_slice, reverse=True):
            if len(picked) + len(by_slice[s]) > k:
                break
            picked.extend(by_slice[s])
        if len(picked) == k:
            picked_ids = {d.id for d in picked}
            train = [d for d in devices if d.id not in picked_ids]
            return (sorted(train, key=lambda d: d.id),
                    sorted(picked, key=lambda d: d.id))
    ordered = sorted(devices, key=lambda d: d.id)
    train, roll = ordered[:-k], ordered[-k:]
    if all(hasattr(d, "slice_index") for d in devices) \
            and len({d.slice_index for d in devices}) > 1:
        # multi-slice pod and the whole-slice reservation failed. Two
        # distinct fallout modes (single-slice hosts are skipped entirely —
        # every link there is ICI and there is nothing to warn about):
        import warnings

        roll_slices = {d.slice_index for d in roll}
        if len(roll_slices) > 1:
            # rollout mesh spans slices: its OWN collectives (and they run
            # every decode step) now cross DCN — the expensive case
            warnings.warn(
                f"split_rollout_devices: no suffix of whole slices sums to "
                f"k={k}; the id-ordered fallback spreads the rollout mesh "
                f"over slices {sorted(roll_slices)}, so rollout-mesh "
                "collectives ride DCN every decode step. Pick "
                "rollout_devices as a multiple of the slice size (the "
                "whole-slice reservation assumes homogeneous slices).",
                RuntimeWarning,
                stacklevel=2,
            )
        else:
            # rollout fits inside one slice (its collectives stay on ICI)
            # but that slice is split with training — the train mesh now
            # has a partial slice, skewing ITS collective topology
            warnings.warn(
                f"split_rollout_devices: no suffix of whole slices sums to "
                f"k={k}; the id-ordered fallback carves the rollout group "
                f"out of slice {sorted(roll_slices)}, leaving the TRAIN "
                "mesh a partial slice (its collectives straddle the cut). "
                "Rollout-internal collectives stay on ICI. Pick "
                "rollout_devices as a multiple of the slice size (the "
                "whole-slice reservation assumes homogeneous slices).",
                RuntimeWarning,
                stacklevel=2,
            )
    return train, roll
