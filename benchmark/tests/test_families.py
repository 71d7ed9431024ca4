"""What a family file answers (``families/<name>.py``): the two that moved
out of the harness give what the harness gave before the move (PR 26's
tree, commit 779dbbe: the hashes and counts below were taken from it), and
a family the harness has never met (``data/toy_family.py``) is loaded by
name with a list among its sizes."""

import hashlib
import json
import os

import numpy as np
import pytest

import control
from vbench import flops, loader, weights

DATA = os.path.join(os.path.dirname(__file__), "data")
TOY = "../tests/data/toy_family"

# sha256 over (name, shape, dtype, bytes) of every tensor, in the draw's
# order, at tiny_fleet.json's sizes: {(family, seed, salt): digest}
PARENT_WEIGHTS = {
    ("vit", 7, 0):
        "ca03103df0f0335a229ad331b50c4223dbdb955ca065699fda2834663409a4d6",
    ("vit", 7, 1):
        "c41a24847d619de7dd93459574464cb4f06121b3f8d49ebe9ca1ec5e3f6af7af",
    ("vit", 2**31 + 9, 0):
        "c3f2c74aa87de3fb44945b8a06bb5ae3db572fea224c40dfa6a9ab7c05ddca6e",
    ("vit", 2**31 + 9, 1):
        "a906ed675af5a6fa906b7269ac308f025e9279b8eb19fa2c122bcdd2901fadc5",
    ("videomae", 7, 0):
        "1d914a754032ee7215ff6ae119fcb5ad52795d290ce94002228971490d98bff8",
    ("videomae", 7, 1):
        "5ac31a6cd7c9e5cfa07cc34d7f85d8c5df1a2e7ec03136f97a166dc2ccfd1ab3",
    ("videomae", 2**31 + 9, 0):
        "d7e33762ef80f0ac80699e10d5b4c0de75703976ed87fa61435a68d2aee8c7dc",
    ("videomae", 2**31 + 9, 1):
        "a270f438fe173201912f3a97e9a67ede816f7efd027d40a07cc26722a1859213",
}
# at the two configurations' own sizes, {(file, model)}: tensors; sha256 of
# the repr of [(name, shape, kind, fan_in)]; sha256 of the repr of the
# sorted sizes; sample_flops at 1080p
PARENT_MODELS = {
    ("videomae_b", "videomae_b"): (
        151,
        "73628d2b2afe3c4ca194d2ece0018389c837ce04ccb1c217791356d0e87f481a",
        "bddf0218602e936898f5041afb00e2b82597c74480dbe7c2056c4d5c66b80fc9",
        184_606_089_216),
    ("tagclip_fleet", "vit_b16"): (
        152,
        "54ea34a42d83985037ac7c3d44df2ceb19353dfe8da0b8acf9b0a4b94d56875f",
        "5d5a7d85777f1173b70fcdc22f08549f650bd0aab555790004d6ac4499b63856",
        38_492_602_368),
    ("tagclip_fleet", "videomae_b"): (
        151,
        "73628d2b2afe3c4ca194d2ece0018389c837ce04ccb1c217791356d0e87f481a",
        "0f042be8e82cd9705f45b88a08dc994e80a97d504969ede193d77daec4901d32",
        184_606_089_216),
}


def _models(path):
    with open(path) as f:
        return {m["registry_model"]: m for m in loader.models(json.load(f))}


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("family,seed,salt", sorted(PARENT_WEIGHTS))
def test_seeded_weights_are_the_parents_bit_for_bit(family, seed, salt):
    m = next(m for m in _models(os.path.join(DATA, "tiny_fleet.json"))
             .values() if m["family"] == family)
    h = hashlib.sha256()
    for name, a in weights.generate(seed, family, m["sizes"], salt).items():
        a = np.asarray(a)
        h.update(name.encode())
        h.update(repr((tuple(a.shape), str(a.dtype))).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT_WEIGHTS[family, seed, salt]


@pytest.mark.parametrize("config,model", sorted(PARENT_MODELS))
def test_weight_list_sizes_and_operations_are_the_parents(config, model):
    m = _models(os.path.join(loader.HERE, "configs", config + ".json"))[model]
    n, spec_sha, sizes_sha, ops = PARENT_MODELS[config, model]
    spec = [(name, tuple(int(x) for x in shape), kind, int(fan_in))
            for name, shape, kind, fan_in
            in loader.family(m["family"]).param_spec(m["sizes"])]
    assert len(spec) == n
    assert _sha(repr(spec)) == spec_sha
    # scalars only, as before: no nested group of the file rides along
    assert _sha(repr(sorted(m["sizes"].items()))) == sizes_sha
    assert loader.frozen(m["sizes"]) == tuple(sorted(m["sizes"].items()))
    assert flops.sample_flops(m["family"], m["sizes"], 1080, 1920) == ops


def test_a_familys_structured_sizes_reach_it_and_freeze():
    m = _models(os.path.join(DATA, "toy_fleet.json"))["tiny_vit"]
    assert m["family"] == TOY
    assert m["sizes"]["layer_widths"] == [64, 64]
    # the file's other nested groups (engine, limits, roles) are no sizes
    assert all(isinstance(v, (int, float, str, bool))
               for k, v in m["sizes"].items() if k != "layer_widths")
    key = loader.frozen(m["sizes"])
    assert dict(key)["layer_widths"] == (64, 64)
    hash(key)
    assert loader.frozen({"rope": {"theta": 1e6, "kinds": ["a", "b"]}}) \
        == (("rope", (("kinds", ("a", "b")), ("theta", 1e6))),)
    assert loader.family(TOY) is loader.family(TOY)     # loaded once


def test_a_family_brings_a_kind_of_tensor_of_its_own():
    """``wide_head`` is in no harness file: the generator asks the family
    for its spread. The rest of the toy's weights are ``vit``'s, from the
    same draw."""
    toy = _models(os.path.join(DATA, "toy_fleet.json"))["tiny_vit"]
    tiny = _models(os.path.join(DATA, "tiny_fleet.json"))["tiny_vit"]
    kinds = {k for _, _, k, _ in loader.family(TOY).param_spec(toy["sizes"])}
    assert "wide_head" in kinds and "head" not in kinds
    a = weights.generate(11, TOY, toy["sizes"])
    b = weights.generate(11, "vit", tiny["sizes"])
    assert list(a) == list(b)
    for name in a:
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), name
    wide = weights.generate(11, TOY, dict(toy["sizes"], head_std=6.0))
    k = "classifier/kernel"
    assert np.allclose(np.asarray(wide[k]), 2.0 * np.asarray(a[k]))
    with pytest.raises(ValueError):
        loader.family("vit").spread("wide_head", 64, tiny["sizes"])


def test_the_controls_sample_is_the_familys_first_window():
    tiny = _models(os.path.join(DATA, "tiny_fleet.json"))
    toy = _models(os.path.join(DATA, "toy_fleet.json"))["tiny_vit"]
    assert control.first_window(tiny["tiny_vit"], 9) == [9]
    assert control.first_window(tiny["tiny_videomae"], 11) \
        == [11, 48, 85, 122]
    assert control.first_window(toy, 10) == [10, 47, 84]


def test_an_unknown_family_is_a_missing_file():
    with pytest.raises(FileNotFoundError):
        loader.family("no_such_family")
