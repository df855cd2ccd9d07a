"""Plain reference of the CenterPoint sparse backbone (SECOND-style 3D
encoder, as benchmarked in the TorchSparse++ paper), one scene at a time.

Stem: one K=3 submanifold conv.  Stage i: a K=2 stride-2 conv, then
``sub_convs_per_stage`` K=3 submanifold convs; every conv is followed by
batch norm (inference mode, a per-channel affine) and ReLU.  The output
is the last stage's features on its voxels (stride 16 for four stages).
"""
from __future__ import annotations

from chipbench import reference as R


def _ch(model: dict, c: float) -> int:
    return max(8, int(c * model["width"]))


def out_stride(model: dict) -> int:
    return 2 ** len(model["channels"])


def pyramid(coords, model: dict) -> R.Pyramid:
    return R.Pyramid(coords, len(model["channels"]), with_up=False)


def layers(model: dict) -> list:
    """(name, map, Cin, Cout, kernel volume) of every conv layer in order."""
    c0 = _ch(model, model["channels"][0])
    out = [("stem", ("sub", 1), model["in_channels"], c0, 27)]
    cin, s = c0, 1
    for i, c in enumerate(model["channels"]):
        c = _ch(model, c)
        out.append((f"down{i}", ("down", s), cin, c, 8))
        s *= 2
        for b in range(model["sub_convs_per_stage"]):
            out.append((f"sub{i}_{b}", ("sub", s), c, c, 27))
        cin = c
    return out


def forward(params, feats, maps, model: dict, mode: str, cap: int):
    """Features of every output voxel (stride ``out_stride``), rows in
    lexicographic voxel order (padded to ``cap``)."""
    n, maps = maps["n"], maps["maps"]
    x = R.store(feats, mode)
    for name, ref, *_ in layers(model):
        y = R.conv(x, params[name]["w"], maps[ref], mode)
        s = 2 * ref[1] if ref[0] == "down" else ref[1]
        x = R.store(R.bn_relu(y, params[f"{name}_bn"], R.valid(n[s], cap)),
                    mode)
    return x
