"""Family ``vit``: a ViT classifier over single frames (``models/vit.py``).

A family file is everything the harness knows of one model family; the
harness finds it by the ``family`` key of a configuration's group
(``loader.family``). The answers a family gives (``sizes`` is the group's
scalars plus the keys named in ``STRUCTURED_SIZES``):

- ``STRUCTURED_SIZES``  the group's lists and nested groups that are sizes.
- ``param_spec(sizes)`` its weights, [(name, shape, kind, fan_in)] in the
  order of the one draw; ``spread(kind, fan_in, sizes)`` the (mean,
  standard deviation) of each kind it uses.
- ``check_sizes(module, sizes)``  {key: (file, program)} where the
  registry's model differs from the file; empty when they agree.
- ``template(base, module)``  ``jax.eval_shape`` of the module's ``init``:
  the variable tree ``weights.as_variables`` fills.
- ``sample_frames(sizes)``  the frames one sample is made of: a camera is
  owed a result from that many reads on.
- ``window(result, reads, sizes)``  the frame numbers a result depends on,
  from its camera's reads in order (``None``: not to be rebuilt).
  ``result`` is the harness's record of it (``device_id``, ``packet``,
  ``timestamp``, ``model``, ``t`` and ``kept``): a family with state keeps
  there what says where its state was last reset, and returns every read
  since.
- ``REFERENCE_BLOCK`` windows per reference call, ``reference_args(buf,
  windows, sizes)`` the reference's arguments from ``buf`` [block, frames
  of the longest window, H, W, 3] uint8, whose first rows hold ``windows``
  (frame numbers, a list a row).
- ``sample_flops(sizes, h, w)``  the operations one served sample needs.
- ``kept(res)`` what of a served result is compared, ``as_served(row)``
  the same from a reference row (the control), and ``compare(served,
  rows, model)`` the named numbers, whose limits are in the configuration.
"""

from vbench import correct

from families import _encoder
from families._encoder import (STRUCTURED_SIZES, as_served,  # noqa: F401
                               compare, kept, spread, template)

REFERENCE_BLOCK = 16        # single 1080p frames per reference call


def param_spec(sizes):
    d, ps = sizes["hidden_size"], sizes["patch_size"]
    g = sizes["image_size"] // ps
    out = []
    _encoder.dense(out, "patch_embed", ps * ps * 3, d, shape=(ps, ps, 3, d))
    out.append(("cls_token", (1, 1, d), "table", 0))
    out.append(("pos_embed", (1, g * g + 1, d), "table", 0))
    return _encoder.encoder_and_head(out, sizes, "classifier")


def check_sizes(module, sizes):
    return _encoder.disagree(_encoder.program_sizes(module), sizes)


def sample_frames(sizes):
    return 1


def window(result, reads, sizes):
    return correct.last_reads(result, reads, 1)


def reference_args(buf, windows, sizes):
    return (buf[:, 0],)


def sample_flops(sizes, src_h, src_w):
    """One frame: 196 patches and the class token at 224 / 16."""
    d, ps = sizes["hidden_size"], sizes["patch_size"]
    grid = (sizes["image_size"] // ps) ** 2
    embed = 2 * grid * (ps * ps * 3) * d
    return _encoder.model_flops(sizes, 1, grid + 1, embed, src_h, src_w)
