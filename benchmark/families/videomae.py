"""Family ``videomae``: a VideoMAE classifier over sliding clips of
``num_frames`` frames (``models/videomae.py``). ``vit.py`` lists the
answers a family gives."""

from vbench import correct

from families import _encoder
from families._encoder import (STRUCTURED_SIZES, as_served,  # noqa: F401
                               compare, kept, spread, template)

REFERENCE_BLOCK = 4         # clips per reference call (8 x 1080p frames each)


def param_spec(sizes):
    d, ps, ts = sizes["hidden_size"], sizes["patch_size"], sizes["tubelet_size"]
    g = sizes["image_size"] // ps
    out = []
    _encoder.dense(out, "tubelet/proj", ts * ps * ps * 3, d,
                   shape=(ts, ps, ps, 3, d))
    out.append(("pos_embed",
                (1, (sizes["num_frames"] // ts) * g * g, d), "table", 0))
    return _encoder.encoder_and_head(out, sizes, "head")


def check_sizes(module, sizes):
    got = _encoder.program_sizes(module)
    got["num_frames"] = module.cfg.num_frames
    got["tubelet_size"] = module.cfg.tubelet_size
    return _encoder.disagree(got, sizes)


def sample_frames(sizes):
    return int(sizes["num_frames"])


def window(result, reads, sizes):
    """The camera's last ``num_frames`` READ frames, the answered one
    last."""
    return correct.last_reads(result, reads, sample_frames(sizes))


def reference_args(buf, windows, sizes):
    return (buf,)


def sample_flops(sizes, src_h, src_w):
    """One clip: (frames / tubelet) x 14 x 14 tubelet tokens."""
    d, ps, ts = sizes["hidden_size"], sizes["patch_size"], sizes["tubelet_size"]
    frames = sizes["num_frames"]
    tokens = (frames // ts) * (sizes["image_size"] // ps) ** 2
    embed = 2 * tokens * (ts * ps * ps * 3) * d
    return _encoder.model_flops(sizes, frames, tokens, embed, src_h, src_w)
