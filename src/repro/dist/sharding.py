"""Partition specs for the production meshes (the distribution layer).

Mesh axis conventions
---------------------
Two production meshes are supported (see ``repro.launch.mesh``):

* ``pod16x16``   — axes ``("data", "model")``, 256 chips (one pod)
* ``pod2x16x16`` — axes ``("pod", "data", "model")``, 512 chips (two pods)

Axis roles:

* ``model`` — tensor-parallel axis.  Shards the hidden/ff/head/vocab dim of
  weight matrices (Megatron-style), the kv-head or head_dim of decode
  caches, and the vocab dim of logits.
* ``data`` — data-parallel axis.  Shards the batch dim of every input and
  cache; under the ``fsdp`` sharding profile it additionally shards one
  weight dim of each parameter (so parameters are gathered on use).
* ``pod`` — outermost data-parallel axis of the multi-pod mesh.  Batch and
  FSDP sharding use ``("pod", "data")`` combined when divisible.  It is
  also the natural slot axis for the ensemble layer: one replica-exchange
  member per pod (see ``repro.dist.topology``).
* ``slot`` — leading axis of a *multi-slot* submesh returned by
  ``SlotTopology.submesh``; treated as an additional (outermost)
  data-parallel axis, so a task spanning k slots gets k-fold wider batch
  sharding.

Per-arch behaviour is selected by ``cfg.sharding_profile``:

* ``fsdp``  — 2D: tensor-parallel over ``model`` + parameter sharding over
  the data axes (minicpm, gemma2/3, recurrentgemma, whisper).
* ``tp``    — tensor-parallel only; parameters replicated across the data
  axes (nemotron, internvl, falcon-mamba, grok's giant experts).
* ``tp_ep`` — like ``tp`` but MoE expert weights are sharded over ``model``
  on the *expert* dim (expert parallelism; qwen3-moe, E=128).

Divisibility-fallback rule
--------------------------
A dim is sharded over a mesh axis (or axis tuple) only when its size is
*exactly divisible* by the axis size — jit input shardings require it.
Every placement therefore tries an ordered list of candidate dims and axis
groups and takes the first exact fit; when nothing fits, the dim (or the
whole leaf) stays replicated.  Example: minicpm-2b's vocab 122753 is not
divisible by 16, so the vocab-parallel embedding falls back to sharding
d_model=2304 over ``model`` and leaves the vocab dim whole; long_500k's
batch of 1 leaves the batch dim unsharded.  No mesh axis is ever assigned
to two dims of the same array.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

MODEL_AXIS = "model"
# widest-first; "slot" is the leading axis of a multi-slot submesh built by
# repro.dist.topology.SlotTopology.submesh (extra data parallelism for tasks
# spanning several pilot slots)
DATA_AXES = ("slot", "pod", "data")

# Leaf names that are always replicated: norms/gains/biases and small
# per-channel vectors (gathering them is cheaper than the bookkeeping).
_REPLICATED_LEAVES = frozenset({
    "scale", "bias", "q_norm", "k_norm", "a_param", "dt_bias", "D",
    "conv_b", "router", "pos",
})


# ---------------------------------------------------------------- mesh utils

def abstract_mesh(shape: Sequence[int], axes: Sequence[str]) -> AbstractMesh:
    """AbstractMesh((16, 16), ("data", "model")): a mesh of no devices."""
    return AbstractMesh(tuple(shape), tuple(axes))


def shardable_recarve_counts(topology) -> List[int]:
    """Slot counts reachable by ``SlotTopology.recarve`` that keep the
    sharding contract intact.

    ``recarve`` grows by splitting the FIRST slot axis.  When that axis is
    the tensor-parallel ``model`` axis, any split would change the axis
    size every weight matrix was sharded against — existing ``tp``/``fsdp``
    placements become invalid mid-run — so only the current count
    survives.  Splitting a data axis (``data``/``pod``/``slot``) only
    narrows batch parallelism, which the divisibility-fallback rule
    already tolerates, so every topologically reachable count is fine.
    The static validator (repro.analysis, E108) checks cores requests
    against THIS list, not the raw topological one."""
    counts = topology.reachable_slot_counts()
    if topology.axis_names and topology.axis_names[0] == MODEL_AXIS:
        return [topology.n_slots]
    return counts


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """{axis name: size} for Mesh and AbstractMesh alike."""
    try:
        return dict(mesh.shape)
    except TypeError:
        return dict(zip(mesh.axis_names, mesh.axis_sizes))


def data_axis_groups(mesh) -> List[Tuple[str, ...]]:
    """Candidate data-parallel axis groups, widest first."""
    present = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    groups: List[Tuple[str, ...]] = []
    if len(present) > 1:
        groups.append(present)
    groups.extend((a,) for a in reversed(present))  # "data" before "pod"
    return groups


def _group_size(sizes: Dict[str, int], group: Tuple[str, ...]) -> int:
    return math.prod(sizes[a] for a in group)


def _entry(group: Tuple[str, ...]):
    return group[0] if len(group) == 1 else group


def _assign(entries: List[Any], used: set, shape: Tuple[int, ...],
            dims: Sequence[int], groups: Sequence[Tuple[str, ...]],
            sizes: Dict[str, int]) -> None:
    """Place the first group that exactly divides one of ``dims``.

    ``dims`` are tried in preference order; a dim that is already assigned
    or indivisible falls through to the next candidate (the fallback rule).
    """
    for d in dims:
        if d < 0 or d >= len(shape) or entries[d] is not None:
            continue
        for g in groups:
            if any(a in used for a in g):
                continue
            if shape[d] % _group_size(sizes, g) == 0:
                entries[d] = _entry(g)
                used.update(g)
                return


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "name",
                                               getattr(k, "idx", k))))
                 for k in path)


# ---------------------------------------------------------------- params

def _param_dim_prefs(cfg: ModelConfig, names: Tuple[str, ...],
                     shape: Tuple[int, ...]) -> Tuple[List[int], List[int]]:
    """(tensor-parallel dim candidates, fsdp dim candidates) for a leaf.

    Dims are counted from the RIGHT so scanned stacks — which carry a
    leading (num_groups,) dim from vmap/scan — use the same rules as
    unscanned blocks.
    """
    nd = len(shape)
    leaf = names[-1] if names else ""
    parent = names[-2] if len(names) >= 2 else ""
    if nd < 2 or leaf in _REPLICATED_LEAVES:
        return [], []
    r = lambda i: nd + i  # noqa: E731  (negative offset -> absolute dim)

    if leaf == "tok" or parent == "embed":       # (V, D): vocab-parallel
        return [r(-2), r(-1)], [r(-1), r(-2)]
    if leaf == "head":                           # (D, V)
        return [r(-1), r(-2)], [r(-2), r(-1)]
    if parent in ("attn", "xattn"):
        if leaf == "wo":                         # (q_dim, D)
            return [r(-2)], [r(-1)]
        return [r(-1)], [r(-2)]                  # wq/wk/wv: (D, out)
    if parent == "moe":
        if cfg.sharding_profile == "tp_ep":      # expert-parallel: (E, ·, ·)
            return [r(-3)], []
        if leaf == "wo":                         # (E, F, D): TP on F
            return [r(-2), r(-3)], [r(-1)]
        return [r(-1), r(-3)], [r(-2)]           # wi/wg: (E, D, F)
    if parent in ("mlp", "rec"):
        if leaf == "wo":                         # (F, D) / (W, D)
            return [r(-2)], [r(-1)]
        return [r(-1)], [r(-2)]                  # wi/wg/wx/wy/wa/wi_g/conv_w
    if parent == "mamba":
        if leaf in ("x_proj", "out_proj", "A_log"):   # (d_inner, ·)
            return [r(-2)], [r(-1)]
        return [r(-1)], [r(-2)]                  # in_proj/conv_w/dt_proj
    # unknown leaf: prefer the largest dims
    order = sorted(range(nd), key=lambda d: -shape[d])
    return order, list(order)


def param_spec(cfg: ModelConfig, mesh, path: Sequence[Any],
               shape: Sequence[int]) -> P:
    """PartitionSpec for one parameter/optimizer leaf.

    ``path`` is the pytree key path (or a tuple of names like
    ``("embed", "tok")``); rules key on the trailing two names so the same
    spec serves params, grads and Adam moments.
    """
    names = _path_names(path)
    shape = tuple(shape)
    sizes = mesh_axis_sizes(mesh)
    entries: List[Any] = [None] * len(shape)
    used: set = set()
    tp_dims, dp_dims = _param_dim_prefs(cfg, names, shape)
    if MODEL_AXIS in sizes and tp_dims:
        _assign(entries, used, shape, tp_dims, [(MODEL_AXIS,)], sizes)
    if cfg.sharding_profile == "fsdp" and dp_dims:
        _assign(entries, used, shape, dp_dims, data_axis_groups(mesh), sizes)
    return P(*entries)


def state_shardings(cfg: ModelConfig, mesh, specs):
    """NamedShardings for a params / train-state / opt-state pytree.

    ``specs`` is any pytree of arrays or ShapeDtypeStructs (e.g. the output
    of ``train_state_specs`` or ``jax.eval_shape(init_params)``).
    """
    def one(path, x):
        return NamedSharding(
            mesh, param_spec(cfg, mesh, _path_names(path), tuple(x.shape)))
    return jax.tree_util.tree_map_with_path(one, specs)


# ---------------------------------------------------------------- batches

def _batch_spec(mesh, shape: Tuple[int, ...],
                sizes: Optional[Dict[str, int]] = None) -> P:
    """Batch dim 0 over the widest divisible data-axis group; rest whole."""
    sizes = sizes or mesh_axis_sizes(mesh)
    entries: List[Any] = [None] * len(shape)
    if shape:
        _assign(entries, set(), shape, [0], data_axis_groups(mesh), sizes)
    return P(*entries)


def batch_shardings(cfg: ModelConfig, mesh, specs, kind: str = "train"):
    """NamedShardings for a model-input pytree (tokens/labels/positions/...).

    All input leaves are batch-major, so every leaf gets its batch dim
    sharded over the data axes when divisible (long_500k's batch of 1 stays
    replicated).  ``kind`` ("train" | "prefill" | "decode" | "serve") is
    accepted for future kind-specific layouts (e.g. sequence sharding).
    """
    del kind
    sizes = mesh_axis_sizes(mesh)

    def one(x):
        return NamedSharding(mesh, _batch_spec(mesh, tuple(x.shape), sizes))
    return jax.tree.map(one, specs)


# ---------------------------------------------------------------- caches

def cache_shardings(cfg: ModelConfig, mesh, specs):
    """NamedShardings for a decode-cache pytree (``repro.serve.cache_specs``).

    kv caches, (B, S, KH*Dh) with the kv heads side by side, shard batch
    over the data axes and that lane axis over ``model``; recurrent/SSM
    states shard batch and the channel dim.  ``pos`` rings are
    replicated.
    """
    sizes = mesh_axis_sizes(mesh)

    def one(path, x):
        names = _path_names(path)
        leaf = names[-1]
        shape = tuple(x.shape)
        nd = len(shape)
        if leaf in ("k", "v", "xk", "xv") and nd >= 3:
            batch_dim, tp_dims = nd - 3, [nd - 1]           # (B, S, KH*Dh)
        elif leaf == "h" and cfg.ssm_state and nd >= 3:
            batch_dim, tp_dims = nd - 3, [nd - 2, nd - 1]   # (B, d_inner, n)
        elif leaf == "h" and not cfg.ssm_state and nd >= 2:
            batch_dim, tp_dims = nd - 2, [nd - 1]           # (B, lru_width)
        elif leaf == "conv" and nd >= 3:
            batch_dim, tp_dims = nd - 3, [nd - 1]           # (B, cw-1, C)
        else:
            return NamedSharding(mesh, P())
        entries: List[Any] = [None] * nd
        used: set = set()
        if MODEL_AXIS in sizes:
            _assign(entries, used, shape, tp_dims, [(MODEL_AXIS,)], sizes)
        _assign(entries, used, shape, [batch_dim], data_axis_groups(mesh),
                sizes)
        return NamedSharding(mesh, P(*entries))
    return jax.tree_util.tree_map_with_path(one, specs)


# ---------------------------------------------------- in-graph constraints

def _constrain(x, mesh, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_batch(cfg: ModelConfig, mesh, x, kind: str = "train"):
    """Constrain an activation (batch-major) to the data-parallel layout.

    Identity when ``mesh`` is None (single-device tests).  Divisibility is
    re-derived from the traced shape, so microbatched slices (B // nmb)
    resolve their own fallback.
    """
    del kind
    if mesh is None:
        return x
    return _constrain(x, mesh, _batch_spec(mesh, tuple(x.shape)))


def constrain_logits(cfg: ModelConfig, mesh, logits):
    """Constrain (..., V) logits: batch over data axes, vocab over model.

    The vocab dim falls back to replicated when V is indivisible
    (minicpm-2b's 122753).
    """
    if mesh is None:
        return logits
    shape = tuple(logits.shape)
    sizes = mesh_axis_sizes(mesh)
    entries: List[Any] = [None] * len(shape)
    used: set = set()
    if len(shape) >= 2 and MODEL_AXIS in sizes:
        _assign(entries, used, shape, [len(shape) - 1], [(MODEL_AXIS,)],
                sizes)
    _assign(entries, used, shape, [0], data_axis_groups(mesh), sizes)
    return _constrain(logits, mesh, P(*entries))


def constrain_like_params(cfg: ModelConfig, mesh, tree):
    """Constrain a params-shaped pytree (gradients) to the param layout."""
    if mesh is None:
        return tree

    def one(path, g):
        return _constrain(
            g, mesh, param_spec(cfg, mesh, _path_names(path), tuple(g.shape)))
    return jax.tree_util.tree_map_with_path(one, tree)
