"""Operations and bytes of the zoo's ResNet50, from shapes.

The geometry is the zoo's (``deeplearning4j_tpu/models/zoo.py`` after
DL4J's ``ResNet50.java``), which is not the paper's in two places: the
stem is zero-padded by 3 and convolved and pooled without further padding
(224 -> 112 -> 55), and stage 2's first block strides by 2 (55 -> 28), so
the four stages run at 28, 14, 7 and 4 pixels where the paper's run at 56,
28, 14 and 7. A forward image is 1.17 G multiply-adds against the paper's
4.1 G. ``paper_geometry=True`` gives the paper's table, kept so that the
count can be checked against the published number.
"""

from __future__ import annotations


def _out(size, k, s, mode, pad=0):
    if mode == "same":
        return -(-size // s)
    return (size + 2 * pad - k) // s + 1


def layer_table(sizes: dict, paper_geometry: bool = False) -> list:
    """Every layer with weights or a shape change, in forward order:
    dicts with ``name``, ``kind`` (conv, bn, maxpool, dense) and shapes.
    A conv holds ``k``, ``cin``, ``cout``, ``stride``, ``mode``, ``hin``,
    ``hout`` (square images)."""
    h = sizes["image"][0]
    cin = sizes["image"][2]
    rows = []

    def conv(name, k, cout, stride, mode, hin, cin, pad=0):
        hout = _out(hin, k, stride, mode, pad)
        rows.append(dict(name=name, kind="conv", k=k, cin=cin, cout=cout,
                         stride=stride, mode=mode, pad=pad, hin=hin,
                         hout=hout))
        rows.append(dict(name=name + "_bn", kind="bn", c=cout, h=hout))
        return hout

    if paper_geometry:
        h = conv("stem_cnn1", 7, sizes["stem_width"], 2, "same", h, cin)
        hp = _out(h, 3, 2, "same")
    else:
        h = h + 2 * sizes["stem_zero_pad"]
        h = conv("stem_cnn1", 7, sizes["stem_width"], 2, "truncate", h, cin)
        hp = _out(h, 3, 2, "truncate")
    rows.append(dict(name="stem_maxpool1", kind="maxpool", k=3, stride=2,
                     c=sizes["stem_width"], hin=h, hout=hp,
                     mode="same" if paper_geometry else "truncate"))
    h, c = hp, sizes["stem_width"]
    for si, (f1, f2, f3, blocks) in enumerate(sizes["stages"]):
        stage = si + 2
        for bi in range(blocks):
            n = f"res{stage}{'abcdefgh'[bi]}"
            first = bi == 0
            stride = 2 if first and not (paper_geometry and si == 0) else 1
            hin = h
            h2 = conv(n + "_2a", 1, f1, stride, "truncate", hin, c)
            conv(n + "_2b", 3, f2, 1, "same", h2, f1)
            conv(n + "_2c", 1, f3, 1, "truncate", h2, f2)
            if first:
                conv(n + "_1", 1, f3, stride, "truncate", hin, c)
            h, c = h2, f3
    rows.append(dict(name="output", kind="dense", cin=c,
                     cout=sizes["num_labels"]))
    return rows


def forward_macs(sizes: dict, paper_geometry: bool = False) -> int:
    """Multiply-adds of one image's forward pass: convolutions and the
    dense head. Batch norm, pooling and the adds count nothing."""
    total = 0
    for r in layer_table(sizes, paper_geometry):
        if r["kind"] == "conv":
            total += r["hout"] ** 2 * r["k"] ** 2 * r["cin"] * r["cout"]
        elif r["kind"] == "dense":
            total += r["cin"] * r["cout"]
    return total


def train_flops_per_sample(sizes: dict) -> float:
    """Operations a training step needs per image: 2 per multiply-add,
    forward once and backward twice (towards inputs and towards weights).
    Recomputed work does not count."""
    return 3.0 * 2.0 * forward_macs(sizes)


def parameter_count(sizes: dict) -> int:
    n = 0
    for r in layer_table(sizes):
        if r["kind"] == "conv":
            n += r["k"] ** 2 * r["cin"] * r["cout"] + r["cout"]
        elif r["kind"] == "bn":
            n += 2 * r["c"]
        elif r["kind"] == "dense":
            n += r["cin"] * r["cout"] + r["cout"]
    return n
