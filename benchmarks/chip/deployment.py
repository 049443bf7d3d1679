"""A configuration file, read: the tensors of one deployment and their slices.

A configuration (``configs/<name>.json``) holds a public model's sizes under
the keys of its published ``config.json``, the tensors of its checkpoint
with their shapes written in those keys, the split rules of each tensor
layout, and the deployment: how the trainer and the rollout are split, and
which slices this process holds. Nothing here touches JAX or the program.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Tuple

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Slice:
    """One tensor's block held by one shard: its global shape, its offset
    in global coordinates and its local shape."""

    name: str
    gshape: Tuple[int, ...]
    offset: Tuple[int, ...]
    shape: Tuple[int, ...]
    #: position of the tensor in the checkpoint's order (seeds its bits)
    index: int

    @property
    def size(self) -> int:
        return math.prod(self.shape)

    @property
    def layout(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """``(global_shape, offset)``, as ``ShardHandle.register`` takes it."""
        return self.gshape, self.offset


def load_config(name: str, root: Path = HERE) -> dict:
    path = root / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    with open(path) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"{path}: names itself {cfg.get('name')!r}, not {name!r}")
    return cfg


def _dim(cfg: dict, term) -> int:
    """A shape entry: an int, a key of the configuration, or keys joined
    by ``*`` (``num_attention_heads*head_dim``)."""
    if isinstance(term, int):
        return term
    return math.prod(int(cfg[k]) for k in term.split("*"))


def tensors(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(name, global shape, split kind)`` of every tensor, in the
    checkpoint's order: the entries before the first per-layer one, then
    the per-layer block (names holding ``{layer}``) once per layer, then
    the rest."""
    spec = cfg["tensors"]
    per = [i for i, t in enumerate(spec) if "{layer}" in t["name"]]
    if not per or per != list(range(per[0], per[-1] + 1)):
        raise ValueError(f"{cfg['name']}: per-layer tensors must be one block")
    head, block, tail = spec[: per[0]], spec[per[0] : per[-1] + 1], spec[per[-1] + 1 :]
    entries = list(head)
    for layer in range(int(cfg["num_hidden_layers"])):
        entries += [dict(t, name=t["name"].format(layer=layer)) for t in block]
    entries += tail
    return [
        (t["name"], tuple(_dim(cfg, d) for d in t["shape"]), t["split"])
        for t in entries
    ]


def shard_slices(cfg: dict, split: str, tp: int, shard: int) -> List[Slice]:
    """The blocks one TP shard holds under the named split rule. A rule
    maps each split kind to the axis it cuts, or ``null`` for a tensor
    every shard holds whole."""
    rule = cfg["splits"][split]
    out = []
    for index, (name, gshape, kind) in enumerate(tensors(cfg)):
        axis = rule[kind]
        if axis is None:
            out.append(Slice(name, gshape, (0,) * len(gshape), gshape, index))
            continue
        if gshape[axis] % tp:
            raise ValueError(f"{name} {gshape}: axis {axis} does not split {tp} ways")
        per = gshape[axis] // tp
        offset = tuple(shard * per if a == axis else 0 for a in range(len(gshape)))
        shape = tuple(per if a == axis else d for a, d in enumerate(gshape))
        out.append(Slice(name, gshape, offset, shape, index))
    return out


def trainer_slices(cfg: dict) -> List[List[Slice]]:
    """The trainer shards this process holds, each a list of blocks."""
    t = cfg["deployment"]["trainer"]
    return [shard_slices(cfg, t["split"], t["tp"], s) for s in t["shards"]]


def rollout_slices(cfg: dict) -> List[Slice]:
    """The rollout shard a chip of this deployment holds."""
    r = cfg["deployment"]["rollout"]
    return shard_slices(cfg, r["split"], r["tp"], r["shard"])


def load_mix(name: str, root: Path = HERE) -> dict:
    path = root / "mixes" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("name") != name:
        raise ValueError(f"{path}: names itself {mix.get('name')!r}, not {name!r}")
    return mix


def load_peaks(kind: str, root: Path = HERE) -> Dict[str, float]:
    """The published peaks of a device kind; an unknown kind is an error."""
    with open(root / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, workload: str, key: str) -> List[dict]:
    """The metrics of one section (``end_to_end`` or ``per_layer``) that
    a cell reports: those without a ``workloads`` key, and those listing it."""
    return [
        m for m in bench[key] if workload in m.get("workloads", [workload])
    ]
