"""``perfbench_tiny.py`` was written when the four-chip training mix was
files and no cell, and adds it to its tiny checkout itself, the way a
later PR would. ``BENCHMARK.json`` now has that cell, so the tiny
checkout gets it from there: the real cell is mapped onto the same tiny
cell, and what ``make_root`` then adds a second time is dropped. (The
file itself is part of the accepted benchmark and is not edited.)"""

import os

import perfbench_tiny as tiny

tiny.CELLS["mistral-7b-cut.pretrain-2k-dp2mp2"] = ("tiny-mistral",
                                                   "tiny-train4")
_make_root = tiny.make_root


def _first_of_each(entries):
    kept = {}
    for e in entries:
        kept.setdefault(e["name"], e)
    return list(kept.values())


def make_root(root):
    _make_root(root)
    path = os.path.join(root, "BENCHMARK.json")
    bench = tiny._load(path)
    for group in ("workloads", "per_layer"):
        bench[group] = _first_of_each(bench[group])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(dict.fromkeys(m["workloads"]))
    tiny._dump(bench, path)
    return root


tiny.make_root = make_root
