"""Which mechanism takes which kind of model (`core/config.MECHANISMS`,
`ModelConfig.require`): every pair written out, and the property a
deny-list cannot have, that a kind no row lists is refused everywhere."""

import pytest

from nanorlhf_tpu.core import config as C
from nanorlhf_tpu.core.config import ModelConfig

# preset -> the kinds it is
PRESETS = {
    "qwen2_tiny": set(),
    "olmoe_tiny": set(),
    "axk1_tiny": {"latent"},
    "smallthinker_tiny": {"window"},
    "lfm2_tiny": {"conv"},
    "falcon_h1_tiny": {"ssm"},
    "granite_h_tiny": {"ssm"},
    "minicpm_sala_tiny": {"linear", "sparse"},
    "trinity_tiny": {"window"},
    "sdar_tiny": {"block"},
    "ouro_tiny": {"loop"},
}
# kind -> what a refusal says of it, and where it points
SAYS = {
    "window": ("window layers", "docs/SWA.md"),
    "sparse": ("sparse-attention layers", "docs/SALA.md"),
    "conv": ("a model with conv layers", "docs/STATE.md"),
    "ssm": ("state-space layers", "docs/SSM.md"),
    "linear": ("linear-attention layers", "docs/SALA.md"),
    "latent": ("latent attention", "docs/MLA.md"),
    "block": ("diffusion over blocks", "docs/BLOCKDIFF.md"),
    "loop": ("looped model", "docs/OURO.md"),
}
PATTERN = {"smallthinker_tiny", "trinity_tiny", "lfm2_tiny", "falcon_h1_tiny",
           "granite_h_tiny", "minicpm_sala_tiny"}
# mechanism -> the presets it refuses (every other it takes)
REFUSES = {
    "a rollout": {"sdar_tiny"},
    "decode_step": {"sdar_tiny"},
    "a page pool of one kind": PATTERN | {"sdar_tiny"},
    "the serving session": set(),
    "a radix prefix hit": PATTERN | {"sdar_tiny"},
    "speculative decode": PATTERN | {"sdar_tiny", "ouro_tiny"},
    "kv_cache_quant='int8'": PATTERN | {"axk1_tiny", "sdar_tiny", "ouro_tiny"},
    "a rollout under a mesh": {"sdar_tiny", "ouro_tiny"},
    "a mesh under a decode session": {
        "lfm2_tiny", "falcon_h1_tiny", "granite_h_tiny", "minicpm_sala_tiny",
        "sdar_tiny", "ouro_tiny"},
    "the sequence-parallel forward": PATTERN | {"axk1_tiny", "ouro_tiny"},
    "a LoRA adapter": {"falcon_h1_tiny", "granite_h_tiny",
                       "minicpm_sala_tiny", "sdar_tiny"},
    "training": {"lfm2_tiny", "falcon_h1_tiny", "granite_h_tiny",
                 "minicpm_sala_tiny", "sdar_tiny"},
}


def test_every_mechanism_and_kind_is_written_out_here():
    assert set(REFUSES) == set(C.MECHANISMS)
    assert set(SAYS) == set(C.TRAITS)
    for takes, why in C.MECHANISMS.values():
        assert takes <= set(C.TRAITS) and why


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_traits_of_a_preset(preset):
    assert getattr(ModelConfig, preset)().traits == PRESETS[preset]


@pytest.mark.parametrize("preset", sorted(PRESETS))
@pytest.mark.parametrize("mechanism", sorted(REFUSES))
def test_a_mechanism_takes_a_preset_or_refuses_it_by_name(mechanism, preset):
    cfg = getattr(ModelConfig, preset)()
    who = f"{mechanism} (as its caller names it)"
    if preset not in REFUSES[mechanism]:
        assert cfg.require(who, mechanism) is None
        return
    with pytest.raises(NotImplementedError) as e:
        cfg.require(who, mechanism)
    said = str(e.value)
    assert said.startswith(who) and f"({cfg.model_type})" in said
    lacking = PRESETS[preset] - C.MECHANISMS[mechanism][0]
    assert lacking
    for kind in lacking:
        phrase, doc = SAYS[kind]
        assert phrase in said and doc in said
    for kind in set(SAYS) - lacking:    # and names no kind the model is not
        assert C.TRAITS[kind][0] not in said.split("):")[0]


@pytest.mark.parametrize("mechanism", sorted(REFUSES))
def test_a_kind_no_row_lists_is_refused_by_every_mechanism(mechanism,
                                                          monkeypatch):
    """What the next kind of model gets before anyone builds for it: one
    trait and one phrase, and no mechanism takes it."""
    monkeypatch.setitem(C.TRAITS, "seventh",
                        ("a model of a seventh kind", "docs/SEVENTH.md"))
    monkeypatch.setattr(ModelConfig, "traits", frozenset({"seventh"}))
    with pytest.raises(NotImplementedError, match="a seventh kind") as e:
        ModelConfig.qwen2_tiny().require(mechanism)
    assert "docs/SEVENTH.md" in str(e.value)
    assert str(e.value).startswith(f"{mechanism} on a model of a seventh")
