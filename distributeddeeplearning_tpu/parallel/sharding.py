"""Sharding rules: how arrays are laid out over the mesh.

The reference shards *data* only: per-rank file shards
(``data/tfrecords.py:139`` — ``dataset.shard(hvd.size(), hvd.rank())``) and
``DistributedSampler`` (``imagenet_pytorch_horovod.py:250-254``), with params
replicated by Horovod broadcast.  Here the same contract — batch split over
the data axes, everything else governed by explicit rules — is expressed as
``NamedSharding``s that XLA compiles into ICI/DCN collectives.

Two rule systems live here, and ONLY here (this module is the single home
of ``PartitionSpec`` literals in the repo — ``ddlt lint`` audits coverage):

1. **Logical-axis rules** (flax tradition, training models): a model
   annotates its params with logical names (e.g. ``("embed", "mlp")``) and
   a rule list maps logical names to mesh axes.  DP maps everything to
   ``None`` (replicated); FSDP maps the largest axis to ``"fsdp"``; TP maps
   hidden axes to ``"tensor"``.

2. **The partition-rule layout table** (:data:`LAYOUT_RULES`): a regex
   name→PartitionSpec table that resolves ANY named pytree — serve-path
   transformer params (f32 or int8 ``QTensor`` values *and* scale leaves),
   dense and paged KV caches, engine operands, comm-overlap bucket state,
   drafter weights — by leaf path.  First match wins; scalars replicate;
   a mesh axis is used at most once per leaf; a mapping is dropped when
   the dim size is not divisible by the mesh axis size.  This is what
   makes the ``tensor`` mesh axis real for serving: Megatron-style
   column-parallel qkv/w_in, row-parallel proj/w_out, vocab-parallel
   embed/head — one all-reduce per attention and per MLP sub-block.
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu.parallel.mesh import DATA_AXES

PyTree = Any


def batch_sharding(mesh: Mesh, *, extra_axes: Tuple[Optional[str], ...] = ()) -> NamedSharding:
    """Batch arrays: leading dim split over the data axes (data, fsdp).

    ``extra_axes`` optionally shards trailing dims, e.g. ``("seq",)`` for
    sequence-parallel token dims.
    """
    return NamedSharding(mesh, P(DATA_AXES, *extra_axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch: PyTree) -> PyTree:
    """Place a host-local batch onto the mesh, split over the data axes.

    Single-process: a plain ``device_put`` with the batch sharding.
    Multi-host: each process holds its slice of the global batch and
    ``jax.make_array_from_process_local_data`` assembles the global array —
    the TPU-native analogue of the reference's per-rank ``dataset.shard``
    (SURVEY.md §7 "Hard parts" (a)).
    """
    sharding = batch_sharding(mesh)
    leaves = jax.tree_util.tree_leaves(batch)
    if leaves and all(
        isinstance(x, jax.Array) and x.sharding == sharding for x in leaves
    ):
        # Already placed (e.g. a device-resident benchmark batch): skip the
        # no-op device_put — its dispatch is not free.
        return batch
    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
        batch,
    )


# ---------------------------------------------------------------------------
# Logical-axis parameter sharding (flax partitioning convention).
# ---------------------------------------------------------------------------

# rule sets: logical axis name -> mesh axis (or None = replicate)
RULES_DP: Sequence[Tuple[str, Optional[str]]] = [
    # Pure data parallelism: all params replicated (Horovod semantics).
]

RULES_FSDP: Sequence[Tuple[str, Optional[str]]] = [
    # ZeRO-3-style: shard embeddings/MLP widest axes along fsdp.
    ("embed", "fsdp"),
    ("mlp", "fsdp"),
    ("heads", "fsdp"),
    ("conv_out", "fsdp"),
]

RULES_TP: Sequence[Tuple[str, Optional[str]]] = [
    # Megatron-style: column-parallel then row-parallel projections.
    ("mlp", "tensor"),
    ("heads", "tensor"),
    ("kv", "tensor"),
    ("embed", "fsdp"),
]

RULES_EP: Sequence[Tuple[str, Optional[str]]] = [
    # Expert parallelism: stacked MoE expert weights [E, ...] split across
    # the expert mesh axis; compose with a base rule set, e.g.
    # ``list(RULES_FSDP) + list(RULES_EP)``.
    ("expert", "expert"),
]


def logical_to_spec(
    logical_axes: Tuple[Optional[str], ...],
    rules: Sequence[Tuple[str, Optional[str]]],
    *,
    mesh: Optional[Mesh] = None,
    shape: Optional[Tuple[int, ...]] = None,
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec via rules.

    First matching rule wins; a mesh axis is used at most once per spec
    (XLA requirement); unmatched logical axes replicate.  When ``mesh`` and
    ``shape`` are given, a mapping is dropped (replicate) if the dimension
    size is not divisible by the mesh axis size — small params (biases, few
    attention heads) must not fail to shard a whole model.
    """
    taken = set()
    out = []
    for i, name in enumerate(logical_axes):
        mapped = None
        if name is not None:
            for logical, mesh_axis in rules:
                if logical == name and mesh_axis is not None and mesh_axis not in taken:
                    if (
                        mesh is not None
                        and shape is not None
                        and shape[i] % mesh.shape[mesh_axis] != 0
                    ):
                        continue
                    mapped = mesh_axis
                    taken.add(mesh_axis)
                    break
        out.append(mapped)
    return P(*out)


def param_shardings(
    mesh: Mesh,
    params: PyTree,
    rules: Sequence[Tuple[str, Optional[str]]] = RULES_DP,
    logical_axes: Optional[PyTree] = None,
) -> PyTree:
    """NamedShardings for a parameter tree.

    Without ``logical_axes`` (plain DP models like ResNet) every param is
    replicated — the reference's broadcast-then-allreduce contract
    (``imagenet_pytorch_horovod.py:401-409``).  With logical axes (transformer
    models annotated via ``flax.linen.partitioning``) each leaf's axes map
    through ``rules``.
    """
    if logical_axes is None:
        return jax.tree_util.tree_map(lambda _: replicated(mesh), params)

    def _to_sharding(axes, param):
        if axes is None:
            return replicated(mesh)
        shape = getattr(param, "shape", None)
        return NamedSharding(
            mesh, logical_to_spec(tuple(axes), rules, mesh=mesh, shape=shape)
        )

    return jax.tree_util.tree_map(
        _to_sharding,
        logical_axes,
        params,
        is_leaf=lambda x: isinstance(x, tuple) or x is None,
    )


# ---------------------------------------------------------------------------
# The partition-rule layout table (regex leaf-name -> PartitionSpec).
# ---------------------------------------------------------------------------

#: One table for every named device pytree in the repo.  Entries are
#: ``(regex, partition entries)``: the regex is ``re.search``-ed against the
#: leaf's ``/``-joined key path (QTensor leaves contribute ``values`` /
#: ``scales`` path segments; callers namespace ambiguous trees with a
#: ``prefix`` — ``kv_dense/``, ``kv_paged/``, ``io/``, ``comm/``).  FIRST
#: match wins, so put the specific rule above the general one.  Each
#: partition entry is a mesh axis name, a tuple of axis names, or None;
#: entries shorter than the leaf rank leave trailing dims replicated.
LayoutRules = Tuple[Tuple[str, Tuple[Any, ...]], ...]

LAYOUT_RULES: LayoutRules = (
    # -- KV caches ---------------------------------------------------------
    # dense [slots, L, S, h, hd]: slots over the data axes, heads over
    # tensor; scale leaves ([slots, L, S, h] f32) drop the hd dim.
    (r"^kv_dense/(k|v)$", (DATA_AXES, None, None, "tensor", None)),
    (r"^kv_dense/(k|v)_scale$", (DATA_AXES, None, None, "tensor")),
    # paged [pages+1, L, page_size, h * hd], scales [..., h]: the page axis
    # NEVER shards (the block-table gather must stay chip-local), heads over
    # tensor: a shard of the folded minor axis is a contiguous group of
    # whole heads, the same heads its scales' shard names.
    (r"^kv_paged/(k|v)(_scale)?$", (None, None, None, "tensor")),
    # -- engine operands (``io/`` namespace; before the param rules so
    # ``io/pos`` can never fall through to the [max_len, d] ``pos`` rule).
    # Per-slot vectors ride the data axes (a pure-TP mesh has data size 1,
    # which replicates them); host-derived page plumbing replicates.
    (r"^io/(tokens?|pos|slots?|lengths?|step)$", (DATA_AXES,)),
    (r"^io/(block_tables?|page_ids|k|v|from_(pos|offs)|offsets?|draft_len)$", ()),
    # -- flash-decode kernel operands (``attn/`` namespace): the Pallas
    # path shard_maps over the mesh so each chip's kernel instance runs
    # its LOCAL heads — q/out head dim over tensor, the pages' folded minor
    # axis ([rows, page_size, h * hd]) and the scale leaves likewise, block
    # tables and position matrices replicated (page addressing is chip-local
    # by construction).
    (r"^attn/(q|out)$", (None, None, "tensor", None)),
    (r"^attn/(k|v)_(pages|scale)$", (None, None, "tensor")),
    (r"^attn/(k|v)_own$", (None, "tensor", None)),
    (r"^attn/(tables|posmat)$", ()),
    # the same kernel over the DENSE layout ([slots, S, h, hd] rows viewed
    # as per-shard synthetic pages): slots ride the data axes like the
    # cache they come from, heads over tensor.
    (r"^attn_dense/(q|out|(k|v)_rows)$", (DATA_AXES, None, "tensor", None)),
    (r"^attn_dense/(k|v)_scale$", (DATA_AXES, None, "tensor")),
    (r"^attn_dense/(k|v)_own$", (DATA_AXES, "tensor", None)),
    (r"^attn_dense/posmat$", (DATA_AXES,)),
    # -- serve-path transformer weights (stacked [L, ...]; Megatron TP) ----
    # column-parallel (output width over tensor): qkv, w_in.  QTensor
    # scale leaves (axis=-2 keepdims) keep the same rank, so one rule
    # covers values and scales.
    (r"(^|/)(qkv|w_in)(/(values|scales))?$", (None, None, "tensor")),
    # row-parallel (contraction dim over tensor): proj, w_out.  Their
    # QTensor scales reduce that dim to size 1 — the divisibility drop
    # de-shards it, which is exactly right (scales replicate).
    (r"(^|/)(proj|w_out)(/(values|scales))?$", (None, "tensor", None)),
    (r"(^|/)ln[0-9]+$", ()),
    # vocab-parallel embedding/head: per-chip [V/t, d] and [d, V/t]; the
    # embed gather and the sharded-vocab argmax each cost one collective.
    (r"(^|/)embed(/(values|scales))?$", ("tensor", None)),
    (r"(^|/)head(/(values|scales))?$", (None, "tensor")),
    (r"(^|/)pos$", ()),
    # -- comm-overlap state: flat bucket vectors over the data axes --------
    (r"^comm/", (DATA_AXES,)),
)


def layout_rules_provenance(rules: LayoutRules = LAYOUT_RULES) -> str:
    """Short provenance tag for artifacts: which rule table produced the
    shardings (count + content digest, so a silent table edit is visible
    across committed benchmark revisions)."""
    h = hashlib.sha1(repr(rules).encode()).hexdigest()[:8]
    return f"LAYOUT_RULES#{len(rules)}@{h}"


def tensor_parallel_size(mesh: Optional[Mesh]) -> int:
    """Size of the ``tensor`` axis (1 for no mesh — unsharded serving)."""
    return int(mesh.shape["tensor"]) if mesh is not None else 1


def _key_name(entry: Any) -> str:
    """One path entry -> its name segment."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def leaf_path_name(path: Tuple[Any, ...], prefix: str = "") -> str:
    """``/``-joined key path of a leaf, with optional namespace prefix."""
    name = "/".join(_key_name(k) for k in path)
    if prefix:
        return f"{prefix}/{name}" if name else prefix
    return name


def _leaf_shape(leaf: Any) -> Optional[Tuple[int, ...]]:
    """Leaf shape, or None for shapeless placeholders (no divisibility
    drop and no scalar short-circuit for those — the rule applies as
    written)."""
    shape = getattr(leaf, "shape", None)
    return tuple(shape) if shape is not None else None


def _none_is_leaf(x: Any) -> bool:
    """Treat ``None`` as a leaf: name-only trees (``{"k": None}``) resolve
    by path alone — JAX would otherwise flatten None into empty structure
    and the placeholder would silently skip rule resolution."""
    return x is None


def _entry_axes(entry: Any) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _spec_from_entries(
    entries: Tuple[Any, ...],
    *,
    shape: Optional[Tuple[int, ...]] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """Partition entries -> PartitionSpec for one leaf.

    Enforces the XLA axis-used-once rule (a duplicate axis replicates,
    first use wins) and the divisibility drop (an axis whose size does not
    divide the dim replicates — small leaves must not fail to shard a
    whole tree).  Entries beyond the leaf rank are trimmed.
    """
    if shape is not None:
        entries = entries[: len(shape)]
    taken: set = set()
    out: List[Any] = []
    for i, entry in enumerate(entries):
        axes = _entry_axes(entry)
        kept = []
        for ax in axes:
            if ax in taken:
                continue
            if (
                mesh is not None
                and shape is not None
                and shape[i] % int(mesh.shape[ax]) != 0
            ):
                continue
            kept.append(ax)
        taken.update(kept)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_for(
    name: str,
    *,
    shape: Optional[Tuple[int, ...]] = None,
    rules: LayoutRules = LAYOUT_RULES,
    mesh: Optional[Mesh] = None,
) -> Optional[P]:
    """Resolve one leaf name through the rule table (first match wins).

    Returns None when no rule matches — callers decide whether fallthrough
    replicates (lenient) or raises (strict); the lint audit treats any
    fallthrough on a hot-program tree as a finding.
    """
    if shape is not None and len(shape) == 0:
        return P()  # scalars replicate by construction, never fall through
    for pattern, entries in rules:
        if re.search(pattern, name):
            return _spec_from_entries(entries, shape=shape, mesh=mesh)
    return None


def match_partition_rules(
    tree: PyTree,
    *,
    prefix: str = "",
    rules: LayoutRules = LAYOUT_RULES,
    mesh: Optional[Mesh] = None,
    strict: bool = True,
) -> PyTree:
    """PartitionSpecs for every leaf of ``tree`` (SNIPPETS [1] pattern).

    ``tree`` leaves supply shapes (arrays or ShapeDtypeStructs) for the
    divisibility drop.  ``strict=True`` raises on any non-scalar leaf no
    rule matches — the "forgot to shard the new leaf" bug class dies here
    rather than as a silent replicate-everything regression.
    """
    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_none_is_leaf
    )[0]
    missed = []
    specs = []
    for path, leaf in leaves:
        name = leaf_path_name(path, prefix)
        spec = spec_for(name, shape=_leaf_shape(leaf), rules=rules, mesh=mesh)
        if spec is None:
            missed.append(name)
            spec = P()
        specs.append(spec)
    if missed and strict:
        raise ValueError(
            "no partition rule matches leaf(s) "
            f"{missed} (prefix={prefix!r}) — add a rule to "
            "parallel.sharding.LAYOUT_RULES instead of hand-wiring a "
            "PartitionSpec at the call site"
        )
    treedef = jax.tree_util.tree_structure(tree, is_leaf=_none_is_leaf)
    return jax.tree_util.tree_unflatten(treedef, specs)


def resolve_shardings(
    mesh: Mesh,
    tree: PyTree,
    *,
    prefix: str = "",
    rules: LayoutRules = LAYOUT_RULES,
    strict: bool = True,
) -> PyTree:
    """NamedShardings for every leaf of ``tree`` via the rule table."""
    specs = match_partition_rules(
        tree, prefix=prefix, rules=rules, mesh=mesh, strict=strict
    )
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def io_sharding(
    mesh: Mesh,
    name: str,
    *,
    shape: Optional[Tuple[int, ...]] = None,
    rules: LayoutRules = LAYOUT_RULES,
) -> NamedSharding:
    """NamedSharding for one engine operand (the ``io/`` namespace) —
    scalars replicate, per-slot vectors ride the data axes.  Raises on a
    name the table does not cover (operands are a closed set; an uncovered
    one is a bug, not a replicate-silently case)."""
    spec = spec_for(f"io/{name}", shape=shape, rules=rules, mesh=mesh)
    if spec is None:
        raise ValueError(
            f"no partition rule matches engine operand io/{name} — add it "
            "to parallel.sharding.LAYOUT_RULES"
        )
    return NamedSharding(mesh, spec)


def unmatched_leaves(
    tree: PyTree,
    *,
    prefix: str = "",
    rules: LayoutRules = LAYOUT_RULES,
) -> List[str]:
    """Leaf names with NO matching rule (scalars excluded — they replicate
    by construction).  The ``ddlt lint`` sharding-coverage audit asserts
    this is empty for every registered hot program's operand trees."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_none_is_leaf
    )[0]:
        name = leaf_path_name(path, prefix)
        shape = _leaf_shape(leaf)
        if shape is not None and len(shape) == 0:
            continue
        if spec_for(name, shape=shape, rules=rules) is None:
            out.append(name)
    return out


# ---------------------------------------------------------------------------
# Canonical specs for shard_map call sites (ring/ulysses/pipeline/flash).
# Call sites take their layout from here so every PartitionSpec literal in
# the repo lives in this module.
# ---------------------------------------------------------------------------


def replicated_spec() -> P:
    return P()


def data_spec(*rest: Any) -> P:
    """Leading dim over the data axes, trailing entries as given."""
    return P(DATA_AXES, *rest)


def batch_spec(ndim: int) -> P:
    """Batch tensors: leading dim over the data axes, rest replicated."""
    return P(DATA_AXES, *([None] * (ndim - 1)))


def leading_axis_spec(axis_name: str, ndim: int) -> P:
    """Leading dim over ``axis_name`` (pipeline stages), rest replicated."""
    return P(axis_name, *([None] * (ndim - 1)))


def staged_param_spec(stage_axis: str, partition_dims: Sequence[Optional[str]]) -> P:
    """Stage-stacked params: leading stage dim + per-dim axis names (the
    pipeline ZeRO-3 weight layout)."""
    return P(stage_axis, *partition_dims)


def seq_parallel_specs(axis_name: str) -> Tuple[P, P]:
    """(qkv_spec, mask_spec) for sequence-parallel attention ([B, S, H, D]
    layout): tokens over ``axis_name``, mask keys over the same axis."""
    return (
        P(DATA_AXES, axis_name, None, None),
        P(DATA_AXES, None, None, axis_name),
    )


def tp_attention_specs(
    shape: Optional[Tuple[int, ...]] = None, mesh: Optional[Mesh] = None
) -> Tuple[P, P]:
    """(qkv_spec, mask_spec) for head-sharded attention ([B, S, H, D]
    layout): batch over the data axes, heads over ``tensor``, mask
    replicated across heads.  With the operand ``shape`` and the ``mesh``
    an axis that does not divide its dim replicates instead — a one-
    sequence serving prefill under a data mesh runs whole on every chip."""
    mask_shape = None if shape is None else (shape[0], 1, 1, shape[1])
    return (
        _spec_from_entries(
            (DATA_AXES, None, "tensor", None), shape=shape, mesh=mesh
        ),
        _spec_from_entries(
            (DATA_AXES, None, None, None), shape=mask_shape, mesh=mesh
        ),
    )


def model_logical_axes(model, rng, *example_args, **example_kwargs) -> PyTree:
    """Extract the logical-axis tree from a flax model's partitioning metadata.

    Returns a pytree matching ``params`` whose leaves are tuples of logical
    axis names (flax ``PartitionSpec``s) or None for unannotated params —
    the ``logical_axes`` input to ``param_shardings``.
    """
    import flax.linen as nn
    import jax as _jax

    variables = _jax.eval_shape(lambda: model.init(rng, *example_args, **example_kwargs))
    specs = nn.get_partition_spec(variables)
    return specs["params"]
