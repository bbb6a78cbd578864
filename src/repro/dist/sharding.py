"""Axis-set templates, template resolution, and path-regex sharding rules.

A *template* describes how to shard one array, one entry per leading dim:

  template  ::= [entry, ...]             (may be shorter than the array rank;
                                          trailing dims stay unsharded)
  entry     ::= None                     (this dim is never sharded)
              | [candidate, ...]         (first candidate that fits wins)
  candidate ::= ALL | DP | EP            (named axis set, expanded per mesh)
              | "axis"                   (one mesh axis)
              | ("axis", ...)            (explicit axis tuple)
              | None                     (explicit replicate — stop trying)

Resolution walks dims left to right.  A candidate's axes are filtered to the
ones the mesh actually has AND that earlier dims have not already claimed —
that filtering is the mechanism behind "the cache length shards over 'model'
plus every dp axis the batch leaves idle": ``[ALL, EP, "model"]`` after a
batch dim that claimed 'data' resolves to the remaining axes.  A filtered
candidate fits when its axis-size product exceeds 1 and divides the dim.

Rules are ``(path_regex, template)`` lists applied first-match-wins to a
pytree of shapes (MaxText-style logical rules over path-addressable params);
unmatched leaves replicate.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


class _AxisSet:
    """Named axis-set placeholder, expanded against a concrete mesh."""

    def __init__(self, name: str, members: tuple[str, ...]):
        self.name = name
        self.members = members

    def __repr__(self) -> str:  # template dumps in error messages
        return self.name


# ALL: every mesh axis (mesh order).  DP: the data-parallel set.  EP: the
# expert/row-parallel set — embedding-table rows and stacked experts spread
# over ('data', 'model') so ZeRO-3 storage scales with the whole non-pod mesh.
ALL = _AxisSet("ALL", ())          # members computed from the mesh
DP = _AxisSet("DP", ("pod", "data"))
EP = _AxisSet("EP", ("data", "model"))


def _expand(cand, mesh) -> tuple[str, ...] | None:
    """Candidate -> ordered axis tuple (None means explicit replicate)."""
    if cand is None:
        return None
    if cand is ALL:
        return tuple(mesh.axis_names)
    if isinstance(cand, _AxisSet):
        return tuple(a for a in cand.members if a in mesh.axis_names)
    if isinstance(cand, str):
        return (cand,)
    return tuple(cand)


def resolve_dim(entry, dim: int, mesh, used: set[str]):
    """One template entry -> PartitionSpec entry (claims axes into ``used``)."""
    if entry is None:
        return None
    sizes = dict(mesh.shape)
    for cand in entry:
        axes = _expand(cand, mesh)
        if axes is None:
            return None
        axes = tuple(a for a in axes if a in sizes and a not in used)
        if not axes:
            continue
        prod = int(np.prod([sizes[a] for a in axes]))
        if prod > 1 and dim % prod == 0:
            used.update(axes)
            return axes if len(axes) > 1 else axes[0]
    return None


def resolve_template(template, shape, mesh) -> PartitionSpec:
    """Template + concrete shape + mesh -> PartitionSpec (never fails: dims
    whose candidates don't fit replicate)."""
    used: set[str] = set()
    return PartitionSpec(*[resolve_dim(e, int(d), mesh, used)
                           for d, e in zip(shape, template)])


# -------------------------------------------------------------- rule plumbing

def _key_str(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def tree_path_strings(tree):
    """Flatten with '/a/b/c' path strings (dict keys, namedtuple fields,
    sequence indices all addressable)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/" + "/".join(_key_str(k) for k in kp) for kp, _ in flat]
    return paths, [v for _, v in flat], treedef


def spec_for_path(path: str, shape, rules, mesh) -> PartitionSpec:
    for pat, template in rules:
        if re.search(pat, path):
            return resolve_template(template, shape, mesh)
    return PartitionSpec()


def shardings_for(mesh, tree, rules):
    """Pytree of shapes (arrays or ShapeDtypeStructs) -> NamedSharding pytree,
    first matching rule per leaf path, replicated when nothing matches."""
    paths, leaves, treedef = tree_path_strings(tree)
    shardings = [
        NamedSharding(mesh, spec_for_path(p, getattr(l, "shape", ()), rules, mesh))
        for p, l in zip(paths, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, shardings)


# ---------------------------------------------------------------- rule tables
#
# Optimizer moments mirror the param tree (same path suffixes under mu/nu/
# acc), so one table rules params AND optimizer state; adafactor's factored
# row/col vectors get extra '/v_row' suffixes, fall through, and replicate —
# they are O(n+m) and not worth sharding.

def lm_rules():
    """Transformer params: Megatron tensor parallelism over 'model' for the
    per-layer matmuls (column-parallel QKV/up, row-parallel out/down), ZeRO-3
    (fully-sharded storage) over the dp axes for the other big dim, experts
    and vocab rows over EP.  Leading entry is the stacked layer axis."""
    return [
        # MoE: storage specs MUST match nn/moe.py::_moe_w_specs (the shard_map
        # in_specs) so no resharding happens at the boundary.
        (r"/moe/w_(gate|up)$", [None, [EP, "model", "data"],
                                [DP, "pod", "data"], None]),
        (r"/moe/w_down$", [None, [EP, "model", "data"], None,
                           [DP, "pod", "data"]]),
        (r"/moe/router/", [None, None, None]),
        # attention (GQA): column-parallel QKV, row-parallel output
        (r"/attn/w(q|k|v)/kernel$", [None, [DP, "pod", "data"], ["model"]]),
        (r"/attn/w(q|k|v)/bias$", [None, ["model"]]),
        (r"/attn/wo/kernel$", [None, ["model"], [DP, "pod", "data"]]),
        # attention (MLA): down-projections ZeRO-sharded, up-projections
        # column-parallel (their output dim carries the heads)
        (r"/attn/w(q_a|kv_a)/kernel$", [None, [DP, "pod", "data"], None]),
        (r"/attn/w(q_b|kv_b)/kernel$", [None, None, ["model"]]),
        # FFN (dense and MoE-shared): SwiGLU column/row parallel
        (r"/(ffn|shared)/(gate|up)/kernel$",
         [None, [DP, "pod", "data"], ["model"]]),
        (r"/(ffn|shared)/down/kernel$",
         [None, ["model"], [DP, "pod", "data"]]),
        # vocab: full table rows over 'model' (logits end 'model'-sharded,
        # matching the steps.py logits sharding), LMA memory over 'model'
        (r"/embed/table_0$", [["model"], [DP, "pod", "data"]]),
        (r"/embed/memory$", [["model"]]),
        (r"/lm_head/kernel$", [[DP, "pod", "data"], ["model"]]),
        # norms and everything else: replicated (fall-through default)
    ]


def recsys_rules():
    """RecSys params: the paper's shared memory pool M lives sharded over
    'model' (the sharded_memory lookup's in_spec — zero reshard at the
    shard_map boundary); baseline per-table params row-shard over EP.
    MLP towers are tiny and replicate."""
    return [
        (r"/(embedding|linear)/memory$", [["model"]]),
        (r"/(embedding|linear)/table_\d+$", [[EP, "model", "data", None], None]),
        (r"/embedding/(q|r)_\d+$", [[EP, "model", "data", None], None]),
        (r"/embedding/proj_\d+$", [None, None]),
    ]


def gnn_rules():
    """GAT params are all small (heads x hidden); replicate everything —
    the batch/edge arrays carry the sharding (launch/steps.py)."""
    return []


def buffer_rules():
    """Non-trainable buffers.  The dense D' store rows shard over 'model'
    only: the sharded LMA lookup reconstructs each batch row's D_v set with
    the same mask-local-gather + psum it uses for M, which needs the store
    partitioned by the SAME axis the memory psum runs over (rows sharded
    over a dp axis would be invisible to a 'model'-only psum when the batch
    is dp-sharded)."""
    return [
        (r"/store_sets$", [["model"], None]),
        (r"/store_lengths$", [["model"]]),
        (r"/store_(flat|offsets)$", [None]),   # CSR form never shards evenly
    ]
