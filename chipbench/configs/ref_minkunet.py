"""Plain reference of MinkUNet (MinkowskiEngine's MinkUNet as benchmarked
in the TorchSparse++ paper), one scene at a time.

Stem: two K=3 submanifold convs.  Encoder stage i: a K=2 stride-2 conv,
then ``blocks_per_stage`` residual blocks of two K=3 submanifold convs
(BN + ReLU after the first, BN after the second, ReLU after the sum).
Decoder stage i: a K=2 stride-2 transposed conv back to the skip's
voxels, concatenation ``[up, skip]``, then residual blocks (the first one
has no identity path, its input width differing from its output).  Head:
a linear map to the class logits of every input voxel.  Batch norm runs
in inference mode, as a per-channel affine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import reference as R


def _ch(model: dict, c: float) -> int:
    return max(8, int(c * model["width"]))


def out_stride(model: dict) -> int:
    return 1


def pyramid(coords, model: dict) -> R.Pyramid:
    return R.Pyramid(coords, len(model["enc_channels"]), with_up=True)


def layers(model: dict) -> list:
    """(name, map, Cin, Cout, kernel volume) of every conv layer in order,
    then the head as (name, ("sub", 1), Cin, classes, 1)."""
    c0 = _ch(model, model["enc_channels"][0])
    out = [("stem1", ("sub", 1), model["in_channels"], c0, 27),
           ("stem2", ("sub", 1), c0, c0, 27)]
    cin, s = c0, 1
    skips = [c0]
    for i, ce in enumerate(model["enc_channels"]):
        ce = _ch(model, ce)
        out.append((f"down{i}", ("down", s), cin, ce, 8))
        s *= 2
        for b in range(model["blocks_per_stage"]):
            out.append((f"enc{i}b{b}_1", ("sub", s), ce, ce, 27))
            out.append((f"enc{i}b{b}_2", ("sub", s), ce, ce, 27))
        skips.append(ce)
        cin = ce
    skips.pop()
    n = len(model["dec_channels"])
    for i, cd in enumerate(model["dec_channels"]):
        cd = _ch(model, cd)
        s = 2 ** (n - i - 1)
        out.append((f"up{i}", ("up", s), cin, cd, 8))
        cskip = skips.pop()
        for b in range(model["blocks_per_stage"]):
            out.append((f"dec{i}b{b}_1", ("sub", s),
                        cd + cskip if b == 0 else cd, cd, 27))
            out.append((f"dec{i}b{b}_2", ("sub", s), cd, cd, 27))
        cin = cd
    out.append(("head", ("sub", 1), cin, model["num_classes"], 1))
    return out


def forward(params, feats, maps, model: dict, mode: str, cap: int):
    """Logits of every voxel, rows in the scene's own order (padded to
    ``cap``).  ``maps`` is ``Pyramid.padded(cap)``."""
    n, maps = maps["n"], maps["maps"]
    spec = {name: ref for name, ref, *_ in layers(model)}

    def layer(x, name, relu=True):
        ref = spec[name]
        if ref[0] == "up":
            y = R.conv_up(x, params[name]["w"], *maps[ref], mode)
        else:
            y = R.conv(x, params[name]["w"], maps[ref], mode)
        s = 2 * ref[1] if ref[0] == "down" else ref[1]
        return R.store(R.bn_relu(y, params[f"{name}_bn"], R.valid(n[s], cap),
                                 relu), mode), s

    def residual(x, prefix, s):
        idn = x
        y, _ = layer(x, f"{prefix}_1")
        y, _ = layer(y, f"{prefix}_2", relu=False)
        if idn.shape == y.shape:
            y = y + idn
        return R.store(jnp.where(R.valid(n[s], cap), jax.nn.relu(y), 0), mode)

    x, _ = layer(R.store(feats, mode), "stem1")
    x, s = layer(x, "stem2")
    skips = [x]
    n_enc = len(model["enc_channels"])
    for i in range(n_enc):
        x, s = layer(x, f"down{i}")
        for b in range(model["blocks_per_stage"]):
            x = residual(x, f"enc{i}b{b}", s)
        if i < n_enc - 1:
            skips.append(x)
    for i in range(len(model["dec_channels"])):
        x, s = layer(x, f"up{i}")
        x = jnp.concatenate([x, skips.pop()], axis=1)
        for b in range(model["blocks_per_stage"]):
            x = residual(x, f"dec{i}b{b}", s)
    return R.dense(x, params["head"]["w"], mode)
