"""A family the harness has never met, for the tests: the registry's
``tiny_vit`` described the way a later family will be. It comes in by files
under ``tests/data`` and entries alone, and differs from ``vit`` and
``videomae`` in three ways: a size that is a list (``layer_widths``, one
hidden size a layer; and a kind of tensor of its own, ``wide_head``), a
comparison that is not the top-5 one (the served top-1's probability), and
a window longer than its sample (a result is
rebuilt from its camera's last ``context_reads`` reads; the tiny model has
no state, so the reference answers from the last of them)."""

from vbench import correct, loader

from families import _encoder
from families._encoder import template  # noqa: F401

STRUCTURED_SIZES = ("layer_widths",)
REFERENCE_BLOCK = 4


def vit_sizes(sizes):
    """The same model under the ``vit`` family's keys."""
    widths = list(sizes["layer_widths"])
    if len(set(widths)) != 1:
        raise ValueError(f"one width for every layer, not {widths}")
    return dict(sizes, hidden_size=widths[0], num_hidden_layers=len(widths))


def param_spec(sizes):
    return [(name, shape, "wide_head" if kind == "head" else kind, fan_in)
            for name, shape, kind, fan_in
            in loader.family("vit").param_spec(vit_sizes(sizes))]


def spread(kind, fan_in, sizes):
    if kind == "wide_head":
        return 0.0, sizes["head_std"] * fan_in ** -0.5
    return _encoder.spread(kind, fan_in, sizes)


def check_sizes(module, sizes):
    got = _encoder.program_sizes(module)
    got["layer_widths"] = ([got.pop("hidden_size")]
                           * got.pop("num_hidden_layers"))
    return _encoder.disagree(got, sizes)


def sample_frames(sizes):
    return 1


def window(result, reads, sizes):
    return correct.last_reads(result, reads, sizes["context_reads"])


def reference_args(buf, windows, sizes):
    assert all(len(w) == sizes["context_reads"] for w in windows)
    return (buf,)


def sample_flops(sizes, src_h, src_w):
    return loader.family("vit").sample_flops(vit_sizes(sizes), src_h, src_w)


def kept(res):
    """The served top-1 alone: (class, probability)."""
    d = res.detections[0] if res.detections else None
    return (d.class_id, d.confidence) if d else None


def as_served(row):
    return correct.topk(row, 1)[0]


def compare(served, rows, model):
    """``top1_prob_err_<model>``: the widest |served top-1 probability -
    the reference's probability of that class|."""
    import numpy as np

    errs = []
    for top, row in zip(served, rows):
        if top is None or not 0 <= top[0] < len(row):
            errs.append(1e30)
        else:
            p = float(np.exp(correct.log_softmax(row)[top[0]]))
            errs.append(abs(top[1] - p))
    return {f"top1_prob_err_{model}": max(errs)}
