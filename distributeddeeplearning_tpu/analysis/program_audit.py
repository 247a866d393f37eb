"""Layer 2: jaxpr/HLO audits of the programs that actually run on-device.

The AST layer sees what the *host* does between dispatches; this layer
traces the registered jitted programs on abstract shapes (no execution, so
it runs under ``JAX_PLATFORMS=cpu`` in tier-1) and asserts program-level
invariants the source can't show:

- **callback-in-jit**: no ``io_callback`` / ``pure_callback`` /
  ``debug_callback`` primitive anywhere in a hot program — a callback is
  a host round-trip PER STEP hiding inside the compiled step;
- **donation**: ``donate_argnums`` on the cache/state actually
  materializes as input-output aliasing in the lowered module
  (``tf.aliasing_output``) — a donation silently dropped (e.g. by a
  dtype-changing refactor) doubles steady-state HBM;
- **collective-signature**: the comm-overlap train step issues its
  reduce-scatters INSIDE the accumulation scan (the wire-overlaps-
  backward contract, COMMS_r09) and nothing re-hoists an all-reduce;
  the implicit path's compiled HLO still carries its gradient
  all-reduce;
- **dtype-audit** (the QUANT_r10 regression, machine-checkable): in an
  int8-cache program, dequantized f32 history may exist only as a
  fusable intermediate of the attention math — never stored (written
  back by a scatter/update) and never returned;
- **sharding-coverage**: every cache/param/opt-state leaf (scale leaves
  included) resolves to an explicit sharding — the "forgot to shard the
  new leaf" class (ROADMAP Open item 1) caught structurally.

Programs are registered by building the real engines/steps at tiny
shapes and auditing their OWN jit objects (``engine._decode_jit`` etc.),
so the audit covers the donation flags and program structure production
runs with — not a lint-local reimplementation.
"""

from __future__ import annotations

import dataclasses
import inspect
import logging
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as _jcore
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.analysis.core import Finding

logger = logging.getLogger("ddlt.analysis")

#: audits the LAST run_program_audits() call could not execute on the
#: current backend (e.g. the implicit-path collective check on a
#: single-shard mesh) — lint entry points report these so a clean result
#: is never silently weaker than it looks
_last_skips: List[str] = []


def skipped_audits() -> List[str]:
    """Human-readable descriptions of audits the last run skipped."""
    return list(_last_skips)

#: host-callback primitives banned in hot programs
BANNED_PRIMITIVES = (
    "io_callback", "pure_callback", "debug_callback", "debug_print",
)

#: primitives that STORE their update operand (writing f32 history back
#: through one of these is the materialization the dtype audit bans)
WRITE_PRIMITIVES = ("dynamic_update_slice", "scatter", "scatter-add")

ALIAS_ANNOTATION = "tf.aliasing_output"


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------


def _subjaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    for v in params.values():
        if isinstance(v, _jcore.Jaxpr):
            yield v
        elif isinstance(v, _jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for e in v:
                if isinstance(e, _jcore.Jaxpr):
                    yield e
                elif isinstance(e, _jcore.ClosedJaxpr):
                    yield e.jaxpr


def iter_eqns(jaxpr, stack: Tuple[str, ...] = ()) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield ``(eqn, enclosing primitive-name stack)`` over every eqn,
    recursing into scan/while/cond/shard_map/pjit sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, stack
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub, stack + (eqn.primitive.name,))


def primitive_counts(jaxpr) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for eqn, _ in iter_eqns(jaxpr):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def program_location(jitted) -> Tuple[str, int]:
    """file:line of the traced python function behind a jit object."""
    fn = getattr(jitted, "__wrapped__", None) or jitted
    try:
        code = fn.__code__
        return code.co_filename, code.co_firstlineno
    except AttributeError:
        try:
            return inspect.getsourcefile(fn) or "<program>", 0
        except TypeError:
            return "<program>", 0


def _absify(tree):
    """ShapeDtypeStruct skeleton of a (possibly QTensor-bearing) pytree —
    the abstract arguments every trace/lower call here runs on."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), tree
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------------------------------
# per-program record + checks
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ProgramRecord:
    """One registered jitted program traced on abstract arguments.

    ``donate_min`` is the minimum number of input-output aliased buffers
    the lowered module must carry (0 = no donation expected); ``hot``
    arms the callback ban; ``int8_history_len`` arms the dtype audit with
    the full-history position count of the traced cache.

    ``int8_head_dim`` arms the STRICT intermediate audit (the flash-
    decode contract): no history-shaped float value — ``ndim >= 3``,
    some dim ``>= int8_history_len``, trailing dim ``== int8_head_dim``
    (the K/V-vector signature; scores/probabilities trail the position
    dim and scale tensors trail the head dim, so neither matches) — may
    be *produced by any equation* except the bare int8→float widening
    that feeds a matmul operand.  The legacy gather+dequant programs
    fail this (their scale multiply / own-token select / page reshape
    all emit history-shaped floats), which is exactly why only the
    flash-decode records arm it: the fused programs are the ones
    contractually obliged to keep dequantized history out of existence.
    """

    name: str
    jitted: Any
    args: Tuple[Any, ...]
    donate_min: int = 0
    hot: bool = True
    int8_history_len: Optional[int] = None
    int8_head_dim: Optional[int] = None

    def location(self) -> Tuple[str, int]:
        return program_location(self.jitted)


def check_callbacks(rec: ProgramRecord, traced=None) -> List[Finding]:
    traced = rec.jitted.trace(*rec.args) if traced is None else traced
    path, line = rec.location()
    findings = []
    for eqn, stack in iter_eqns(traced.jaxpr.jaxpr):
        if eqn.primitive.name in BANNED_PRIMITIVES:
            where = "/".join(stack) or "top level"
            findings.append(
                Finding(
                    "callback-in-jit", path, line,
                    f"hot program {rec.name} contains a "
                    f"`{eqn.primitive.name}` primitive ({where}) — a host "
                    "round-trip inside the compiled step",
                    hint="remove the callback/debug print from the jitted "
                    "function (route debug output through the readback the "
                    "step already pays, or an eval-only variant)",
                )
            )
    return findings


def check_donation(rec: ProgramRecord, traced=None) -> List[Finding]:
    if not rec.donate_min:
        return []
    traced = rec.jitted.trace(*rec.args) if traced is None else traced
    path, line = rec.location()
    text = traced.lower().as_text()
    n = text.count(ALIAS_ANNOTATION)
    if n < rec.donate_min:
        return [
            Finding(
                "donation", path, line,
                f"program {rec.name}: expected >= {rec.donate_min} "
                f"donated (input-output aliased) buffers, lowered module "
                f"carries {n} — donation did not materialize",
                hint="check donate_argnums on the jit and that the donated "
                "tree comes back with identical avals (a dtype/shape "
                "change on any leaf silently un-aliases it, doubling "
                "steady-state HBM)",
            )
        ]
    return []


def check_int8_history(rec: ProgramRecord, traced=None) -> List[Finding]:
    """The QUANT_r10 audit: dequantized f32 history must stay a fusable
    intermediate of the attention math.  Machine-checkable form:

    - the program carries at least one int8->float dequant (else the
      audit traced the wrong program — vacuity guard);
    - no int8 input leaf comes back wider (int8 cache stays int8);
    - no f32 *output* is history-shaped unless it matches an f32 input
      leaf exactly (the scale leaves legitimately round-trip);
    - no write primitive stores a history-shaped f32 update (writing
      dequantized history back into any buffer).
    """
    if rec.int8_history_len is None:
        return []
    traced = rec.jitted.trace(*rec.args) if traced is None else traced
    path, line = rec.location()
    hist = rec.int8_history_len
    jaxpr = traced.jaxpr.jaxpr
    findings: List[Finding] = []

    def is_history_f32(aval) -> bool:
        # ANY float width counts: dequantizing history to bf16/f16 and
        # storing/returning it is the same materialization regression,
        # just at half the bytes
        shape = tuple(getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        return (
            dtype is not None
            and jnp.issubdtype(dtype, jnp.floating)
            and len(shape) >= 3
            and any(d >= hist for d in shape)
        )

    in_avals = [v.aval for v in jaxpr.invars]
    out_avals = [v.aval for v in jaxpr.outvars]

    def is_int8_cache(aval) -> bool:
        # cache pool leaves, not int8 token scalars: the stored history
        # always carries >= 3 dims ([slots|pages, L, positions, ...])
        return (
            np.dtype(aval.dtype) == np.int8
            and len(getattr(aval, "shape", ())) >= 3
        )

    in_pool_shapes = [
        tuple(a.shape) for a in in_avals if is_int8_cache(a)
    ]
    out_pool_shapes = [
        tuple(a.shape) for a in out_avals if is_int8_cache(a)
    ]
    for shape in in_pool_shapes:
        if shape in out_pool_shapes:
            out_pool_shapes.remove(shape)
        else:
            findings.append(
                Finding(
                    "dtype-audit", path, line,
                    f"program {rec.name}: int8 cache input {shape} has no "
                    "same-shaped int8 output — the cache leaf came back "
                    "widened (or dropped)",
                    hint="keep the stored cache on the int8 grid; "
                    "dequantize into the attention math only",
                )
            )
    f32_in_shapes = {
        (tuple(a.shape), np.dtype(a.dtype))
        for a in in_avals
        if jnp.issubdtype(a.dtype, jnp.floating)
    }
    for a in out_avals:
        if is_history_f32(a) and (
            (tuple(a.shape), np.dtype(a.dtype)) not in f32_in_shapes
        ):
            findings.append(
                Finding(
                    "dtype-audit", path, line,
                    f"program {rec.name} RETURNS a history-shaped f32 "
                    f"value {tuple(a.shape)} — dequantized history "
                    "materialized as program output",
                    hint="the f32 view of int8 history must die inside the "
                    "attention fusion; return the int8 cache + scales",
                )
            )
    def is_history_vector(aval) -> bool:
        # the STRICT intermediate signature: a K/V-history-shaped float
        # ([..., >=hist positions somewhere, head_dim last]).  Scores/
        # probabilities trail the position dim, scale tensors trail the
        # head count — neither matches, so the attention math itself
        # stays legal while any materialized dequantized history trips.
        shape = tuple(getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        return (
            dtype is not None
            and jnp.issubdtype(dtype, jnp.floating)
            and len(shape) >= 3
            and shape[-1] == rec.int8_head_dim
            and any(d >= hist for d in shape)
        )

    saw_dequant = False
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.invars[0].aval
            if np.dtype(src.dtype) == np.int8 and jnp.issubdtype(
                eqn.params.get("new_dtype", jnp.float32), jnp.floating
            ):
                saw_dequant = True
        if (
            rec.int8_head_dim is not None
            and name not in WRITE_PRIMITIVES
            and name != "dot_general"
            # a contraction RESULT is attention math, not stored history
            # (its operands are what the surrounding checks police);
            # every materialization form the gather path used — scale
            # mul, own-token select, broadcast, page reshape — is an
            # elementwise/layout op and stays banned
        ):
            # intermediate audit (flash-decode contract): the only eqn
            # allowed to EMIT a history-shaped float is the bare
            # int→float widening feeding a matmul read — scale
            # multiplies, selects, broadcasts and page reshapes at
            # history granularity are the materializations the fused
            # kernel exists to delete.  Write primitives are handled by
            # the dedicated WRITES check below.
            widening = name == "convert_element_type" and jnp.issubdtype(
                eqn.invars[0].aval.dtype, jnp.integer
            )
            if not widening:
                for outvar in eqn.outvars:
                    if is_history_vector(outvar.aval):
                        findings.append(
                            Finding(
                                "dtype-audit", path, line,
                                f"program {rec.name} materializes a "
                                "history-shaped float intermediate "
                                f"{tuple(outvar.aval.shape)} via "
                                f"`{name}` — dequantized history exists "
                                "inside the int8 decode program",
                                hint="fold scales into the score/"
                                "probability vectors (or dequantize "
                                "in-tile inside the kernel); only the "
                                "bare int8→float widening may touch "
                                "history shapes",
                            )
                        )
        if name in WRITE_PRIMITIVES:
            for operand in eqn.invars[1:]:
                if is_history_f32(operand.aval):
                    findings.append(
                        Finding(
                            "dtype-audit", path, line,
                            f"program {rec.name} WRITES a history-shaped "
                            f"f32 update {tuple(operand.aval.shape)} via "
                            f"`{name}` — dequantized history stored back",
                            hint="quantize on write; only per-position "
                            "updates may flow into the cache buffers",
                        )
                    )
    if not saw_dequant:
        findings.append(
            Finding(
                "dtype-audit", path, line,
                f"program {rec.name}: int8 audit requested but the program "
                "contains no int8->float dequant — the audit is tracing "
                "the wrong program",
                hint="point the record at the int8-cache variant (or drop "
                "int8_history_len)",
            )
        )
    return findings


def check_program(rec: ProgramRecord) -> List[Finding]:
    traced = rec.jitted.trace(*rec.args)
    findings: List[Finding] = []
    if rec.hot:
        findings += check_callbacks(rec, traced)
    findings += check_donation(rec, traced)
    findings += check_int8_history(rec, traced)
    return findings


# --------------------------------------------------------------------------
# collective-signature contract (comm-overlap train step)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveContract:
    """What the comm-overlap program must look like at the jaxpr level."""

    in_scan_reduce_scatter_min: int  # one per bucket per microbatch
    # psum binds outside the scan that carry anything but scalars: none.
    # The fused metrics pmean is all scalars (jax 0.9.0 binds one psum
    # per leaf of a tree-level pmean, so its bind COUNT means nothing);
    # a hoisted gradient all-reduce is an array.
    psum_outside_scan_max: int = 0
    all_gather_min: int = 1  # params (or grads) return via all-gather


def check_collective_contract(
    jaxpr, contract: CollectiveContract, *, name: str, path: str, line: int
) -> List[Finding]:
    in_scan_rs = outside_rs = psum_outside = all_gathers = 0
    for eqn, stack in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        in_scan = "scan" in stack or "while" in stack
        if prim == "reduce_scatter":
            if in_scan:
                in_scan_rs += 1
            else:
                outside_rs += 1
        elif prim == "psum" and not in_scan:
            if any(getattr(v.aval, "ndim", 0) > 0 for v in eqn.invars):
                psum_outside += 1
        elif prim == "all_gather":
            all_gathers += 1
    findings: List[Finding] = []
    if in_scan_rs < contract.in_scan_reduce_scatter_min:
        findings.append(
            Finding(
                "collective-signature", path, line,
                f"{name}: expected >= "
                f"{contract.in_scan_reduce_scatter_min} reduce-scatter "
                f"ops INSIDE the accumulation scan, found {in_scan_rs} "
                f"(outside-scan: {outside_rs}) — the wire no longer "
                "overlaps the backward",
                hint="issue the per-bucket reduce-scatter inside the scan "
                "body (parallel/comms.reduce_scatter_buckets from the "
                "microbatch grads), not on the accumulated total",
            )
        )
    if psum_outside > contract.psum_outside_scan_max:
        findings.append(
            Finding(
                "collective-signature", path, line,
                f"{name}: {psum_outside} array-valued psum ops outside "
                f"the scan (contract allows "
                f"{contract.psum_outside_scan_max}; the scalar metrics "
                "pmean is not counted) — a hoisted all-reduce crept back in",
                hint="gradient traffic must ride the in-scan reduce-"
                "scatter; only scalar metrics reduce outside it",
            )
        )
    if all_gathers < contract.all_gather_min:
        findings.append(
            Finding(
                "collective-signature", path, line,
                f"{name}: expected >= {contract.all_gather_min} all-gather "
                f"(params return from flat shards), found {all_gathers}",
                hint="gather_flat must reassemble the updated params from "
                "the per-device shards",
            )
        )
    return findings


# --------------------------------------------------------------------------
# sharding coverage
# --------------------------------------------------------------------------


def check_tree_coverage(
    tree_abs, shardings, *, name: str, path: str, line: int
) -> List[Finding]:
    """Every leaf of ``tree_abs`` resolves to an explicit sharding whose
    spec fits the leaf's rank; no stale sharding entries either."""
    from jax.sharding import NamedSharding

    flat_t = {
        jax.tree_util.keystr(kp): leaf
        for kp, leaf in jax.tree_util.tree_flatten_with_path(tree_abs)[0]
    }
    flat_s = {
        jax.tree_util.keystr(kp): s
        for kp, s in jax.tree_util.tree_flatten_with_path(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
        )[0]
    }
    findings: List[Finding] = []
    for key in sorted(set(flat_t) - set(flat_s)):
        findings.append(
            Finding(
                "sharding-coverage", path, line,
                f"{name}: leaf {key} has NO sharding rule — the "
                "'forgot to shard the new leaf' class",
                hint="teach the resolver about the new leaf (scale/state "
                "leaves shard like the values they describe)",
            )
        )
    for key in sorted(set(flat_s) - set(flat_t)):
        findings.append(
            Finding(
                "sharding-coverage", path, line,
                f"{name}: sharding rule for {key} matches no live leaf "
                "(stale rule)",
                hint="drop the rule or restore the leaf",
            )
        )
    for key in sorted(set(flat_t) & set(flat_s)):
        leaf, s = flat_t[key], flat_s[key]
        if not isinstance(s, NamedSharding):
            findings.append(
                Finding(
                    "sharding-coverage", path, line,
                    f"{name}: leaf {key} resolves to "
                    f"{type(s).__name__}, not an explicit NamedSharding",
                    hint="every leaf must resolve to an explicit "
                    "PartitionSpec (replicated is P(), not None)",
                )
            )
            continue
        ndim = len(getattr(leaf, "shape", ()))
        if len(s.spec) > ndim:
            findings.append(
                Finding(
                    "sharding-coverage", path, line,
                    f"{name}: leaf {key} (rank {ndim}) has a rank-"
                    f"{len(s.spec)} PartitionSpec {s.spec}",
                    hint="the spec must not outrank the array",
                )
            )
    return findings


def _source_line(obj) -> Tuple[str, int]:
    try:
        return (
            inspect.getsourcefile(obj) or "<unknown>",
            inspect.getsourcelines(obj)[1],
        )
    except (OSError, TypeError):
        return "<unknown>", 0


def check_rule_fallthrough(
    tree_abs, *, prefix: str, name: str, path: str, line: int
) -> List[Finding]:
    """Every non-scalar leaf of ``tree_abs`` must match a rule in the
    partition-rule layout table (``parallel/sharding.LAYOUT_RULES``) —
    a fallthrough leaf silently replicates, which is the 'forgot to
    shard the new leaf' class at the layout-engine layer (per-chip HBM
    quietly loses its 1/TP factor; no crash, no wrong answer)."""
    from distributeddeeplearning_tpu.parallel import sharding as layout

    findings: List[Finding] = []
    # rules read off the module at CALL time (not the def-time default):
    # the audit must see the table as it currently stands
    for leaf_name in layout.unmatched_leaves(
        tree_abs, prefix=prefix, rules=layout.LAYOUT_RULES
    ):
        findings.append(
            Finding(
                "sharding-coverage", path, line,
                f"{name}: leaf {leaf_name} matches NO rule in the "
                "partition-rule layout table — it would silently "
                "replicate on every chip",
                hint="add a rule to parallel/sharding.LAYOUT_RULES "
                "(scale/state leaves shard like the values they "
                "describe; replicated-BY-DESIGN leaves still need an "
                "explicit terminal rule so the intent is auditable)",
            )
        )
    return findings


def _layout_rules_line() -> Tuple[str, int]:
    """file:line of the LAYOUT_RULES table itself — the fix site for
    every rule-fallthrough finding."""
    from distributeddeeplearning_tpu.parallel import sharding as layout

    path = inspect.getsourcefile(layout) or "<unknown>"
    try:
        for i, text in enumerate(inspect.getsource(layout).splitlines(), 1):
            if text.startswith("LAYOUT_RULES"):
                return path, i
    except OSError:
        pass
    return path, 0


def check_sharding_coverage() -> List[Finding]:
    from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
    from distributeddeeplearning_tpu.serve import kv_cache
    from distributeddeeplearning_tpu.train import step as step_mod

    mesh = create_mesh(MeshSpec())
    findings: List[Finding] = []
    path, line = _source_line(kv_cache.cache_sharding)
    for quantized in (False, True):
        dtype = jnp.int8 if quantized else jnp.float32
        cache_abs = jax.eval_shape(
            lambda dt=dtype: kv_cache.init_cache(
                batch_slots=2, num_layers=2, max_seq=16, num_heads=2,
                head_dim=8, dtype=dt,
            )
        )
        findings += check_tree_coverage(
            cache_abs,
            kv_cache.cache_sharding(mesh, quantized=quantized),
            name=f"cache_sharding(quantized={quantized})",
            path=path, line=line,
        )

    # train-state coverage: every param/opt-state/batch-stats leaf of a
    # real model state resolves through _state_shardings
    from jax.sharding import NamedSharding

    state = _train_fixture().state
    shard_tree = step_mod._state_shardings(mesh, state, [], None)
    spath, sline = _source_line(step_mod._state_shardings)
    for kp, s in jax.tree_util.tree_flatten_with_path(
        shard_tree, is_leaf=lambda x: isinstance(x, NamedSharding)
    )[0]:
        if not isinstance(s, NamedSharding):
            findings.append(
                Finding(
                    "sharding-coverage", spath, sline,
                    f"train state leaf {jax.tree_util.keystr(kp)} resolves "
                    f"to {type(s).__name__}, not an explicit NamedSharding",
                    hint="_state_shardings must cover every TrainState "
                    "leaf (params-shaped opt buffers included)",
                )
            )

    # rule-table fallthrough: every registered hot program's named
    # operand trees — serve params on all three precisions (QTensor
    # values AND scale leaves), drafter weights, both cache layouts x
    # dtypes, and the engine/kernel operand namespaces — must resolve
    # through the partition-rule layout table with no silent
    # replicate-fallthrough leaf.  Findings point at the table itself:
    # the fix is a new rule, not a call-site patch.
    from distributeddeeplearning_tpu.spec.decode import SpeculativeDecoder

    fx = _serve_fixture()
    spec_dec = SpeculativeDecoder(
        fx.dense_f32, drafter="truncated", draft_tokens=2, draft_layers=1
    )
    rpath, rline = _layout_rules_line()
    io_abs = {
        "tokens": _sds((_SLOTS,), jnp.int32),
        "slots": _sds((_SLOTS,), jnp.int32),
        "pos": _sds((_SLOTS,), jnp.int32),
        "block_tables": _sds((_SLOTS, 4), jnp.int32),
    }
    attn_abs = {
        "q": _sds((_SLOTS, 1, _H, _D // _H), jnp.float32),
        "out": _sds((_SLOTS, 1, _H, _D // _H), jnp.float32),
        "k_pages": _sds((5, _PAGE, _D), jnp.float32),
        "v_pages": _sds((5, _PAGE, _D), jnp.float32),
        "k_scale": _sds((5, _PAGE, _H), jnp.float32),
        "v_scale": _sds((5, _PAGE, _H), jnp.float32),
        "tables": _sds((_SLOTS, 4), jnp.int32),
        "posmat": _sds((_SLOTS, 4), jnp.int32),
        "k_own": _sds((_SLOTS, _H, _D // _H), jnp.float32),
        "v_own": _sds((_SLOTS, _H, _D // _H), jnp.float32),
    }
    attn_dense_abs = {
        "q": attn_abs["q"],
        "out": attn_abs["out"],
        "k_rows": _sds((_SLOTS, _SEQ, _H, _D // _H), jnp.float32),
        "v_rows": _sds((_SLOTS, _SEQ, _H, _D // _H), jnp.float32),
        "k_scale": _sds((_SLOTS, _SEQ, _H), jnp.float32),
        "v_scale": _sds((_SLOTS, _SEQ, _H), jnp.float32),
        "posmat": attn_abs["posmat"],
        "k_own": attn_abs["k_own"],
        "v_own": attn_abs["v_own"],
    }
    for tname, tree, prefix in (
        ("serve.params.f32", fx.params, "params"),
        ("serve.params.w_int8", fx.qparams, "params"),
        ("spec.drafter.params", spec_dec.drafter._dparams, "params"),
        ("kv.dense.f32", fx.dense_f32.cache, "kv_dense"),
        ("kv.dense.int8", fx.dense_int8.cache, "kv_dense"),
        ("kv.paged.f32", fx.paged_f32.cache, "kv_paged"),
        ("kv.paged.int8", fx.paged_int8.cache, "kv_paged"),
        ("engine.io", io_abs, "io"),
        ("flash_decode.operands", attn_abs, "attn"),
        ("flash_decode.dense_operands", attn_dense_abs, "attn_dense"),
    ):
        findings += check_rule_fallthrough(
            tree, prefix=prefix, name=tname, path=rpath, line=rline
        )
    return findings


# --------------------------------------------------------------------------
# program registry: real engines/steps at tiny shapes
# --------------------------------------------------------------------------

# disambiguated tiny geometry: history (max_seq) is the LARGEST dim, so
# "some dim >= max_seq" identifies history-shaped values unambiguously
_L, _D, _H, _FF, _V, _SEQ = 2, 16, 2, 24, 48, 64
_SLOTS, _PAGE = 2, 8


class _ServeFixture:
    def __init__(self):
        from distributeddeeplearning_tpu.models.pipelined_transformer import (
            init_params,
        )
        from distributeddeeplearning_tpu.quant.calibrate import quantize_params
        from distributeddeeplearning_tpu.serve.engine import (
            InferenceEngine,
            PagedInferenceEngine,
        )

        self.params = init_params(
            jax.random.key(0), num_layers=_L, d_model=_D, num_heads=_H,
            d_ff=_FF, vocab_size=_V, max_len=_SEQ,
        )
        self.qparams = quantize_params(self.params)
        # default engines resolve decode_kernel "auto" -> "flash": the
        # registry audits the programs production serves with (on this
        # cpu platform the fused-XLA twin; the int8 records arm the
        # strict no-history-f32-intermediate audit those programs are
        # contractually obliged to pass)
        kw = dict(num_heads=_H, batch_slots=_SLOTS, max_seq=_SEQ)
        self.dense_f32 = InferenceEngine(self.params, **kw)
        self.dense_int8 = InferenceEngine(
            self.params, cache_dtype=jnp.int8, **kw
        )
        self.dense_w_int8 = InferenceEngine(self.qparams, **kw)
        pkw = dict(page_size=_PAGE, prefill_chunk=_PAGE, **kw)
        self.paged_f32 = PagedInferenceEngine(self.params, **pkw)
        self.paged_int8 = PagedInferenceEngine(
            self.params, cache_dtype=jnp.int8, **pkw
        )
        # the legacy gather path stays registered (it remains selectable
        # via --decode-kernel gather) under the ORIGINAL dtype audit:
        # its history-granular dequant is its known, documented cost,
        # so the strict intermediate check does not arm here
        self.dense_int8_gather = InferenceEngine(
            self.params, cache_dtype=jnp.int8, decode_kernel="gather",
            **kw,
        )
        self.paged_int8_gather = PagedInferenceEngine(
            self.params, cache_dtype=jnp.int8, decode_kernel="gather",
            **pkw,
        )


class _TrainFixture:
    def __init__(self):
        import optax

        from distributeddeeplearning_tpu.models import get_model
        from distributeddeeplearning_tpu.parallel import MeshSpec, create_mesh
        from distributeddeeplearning_tpu.train.state import (
            create_train_state,
            sgd_momentum,
        )

        self.mesh = create_mesh(MeshSpec())
        model = get_model(
            "bert-base", num_layers=1, hidden_size=32, num_heads=2,
            intermediate_size=64, vocab_size=50, num_classes=3,
            max_position_embeddings=16, dropout_rate=0.0,
            dtype=jnp.float32,
        )
        tx = sgd_momentum(optax.constant_schedule(0.05))
        self.state = create_train_state(
            jax.random.key(0), model, (2, 8), tx, input_dtype=jnp.int32
        )
        self.batch_abs = {
            "input": _sds((16, 8), jnp.int32),
            "label": _sds((16,), jnp.int32),
        }


_SERVE: Optional[_ServeFixture] = None
_TRAIN: Optional[_TrainFixture] = None


def _serve_fixture() -> _ServeFixture:
    global _SERVE
    if _SERVE is None:
        _SERVE = _ServeFixture()
    return _SERVE


def _train_fixture() -> _TrainFixture:
    global _TRAIN
    if _TRAIN is None:
        _TRAIN = _TrainFixture()
    return _TRAIN


def build_program_records() -> List[ProgramRecord]:
    """The serve/spec program registry: prefill + decode (+ insert/chunk/
    scrub) on both cache layouts, the quantized variants, and the spec
    draft/verify/rollback programs — each record auditing the engine's
    own jit object."""
    from distributeddeeplearning_tpu.spec.decode import SpeculativeDecoder

    fx = _serve_fixture()
    i32 = jnp.int32
    slot_vec = _sds((_SLOTS,), i32)
    scalar = _sds((), i32)
    records: List[ProgramRecord] = []

    def cache_abs(engine):
        return _absify(engine.cache)

    def n_cache_leaves(engine):
        return len(jax.tree_util.tree_leaves(engine.cache))

    from distributeddeeplearning_tpu.quant.calibrate import (
        abstract_quantized_params,
    )

    p_abs = _absify(fx.params)
    # the PTQ skeleton via eval_shape — pins the audited QTensor layout
    # to what quantize_params actually produces, with no quant math run
    q_abs = abstract_quantized_params(p_abs)

    # the strict no-history-f32-intermediate audit arms on the FLASH
    # programs only (the fused-kernel contract); the gather variants keep
    # the original output/write checks — their history-granular dequant
    # is the documented cost the flash kernel exists to delete
    _HD = _D // _H
    # the history-vector signature (trailing dim == head_dim) relies on
    # the audit dims keeping head_dim distinct from the head COUNT: a
    # gathered scale tensor trails h, and h == hd would make legal
    # scale tensors indistinguishable from materialized history — fail
    # loudly here rather than with false findings on clean programs
    assert _H != _HD, (
        f"audit dims degenerate: num_heads ({_H}) == head_dim ({_HD}) — "
        "the strict dtype audit's history-vector signature needs them "
        "distinct; adjust _D/_H in program_audit.py"
    )

    # dense engines ------------------------------------------------------
    for tag, engine, params_abs, int8_cache in (
        ("serve.dense.f32", fx.dense_f32, p_abs, False),
        ("serve.dense.int8", fx.dense_int8, p_abs, True),
        ("serve.dense.w_int8", fx.dense_w_int8, q_abs, False),
        ("serve.dense.int8_gather", fx.dense_int8_gather, p_abs, True),
    ):
        c_abs = cache_abs(engine)
        kv = _sds((1, _L, 8, _H, _D // _H), jnp.float32)
        flash = engine.decode_kernel == "flash"
        records += [
            ProgramRecord(
                f"{tag}.prefill", engine._prefill_jit,
                (params_abs, _sds((1, 8), i32), scalar),
            ),
            ProgramRecord(
                f"{tag}.insert", engine._insert_jit,
                (c_abs, kv, kv, scalar),
                donate_min=n_cache_leaves(engine),
            ),
            ProgramRecord(
                f"{tag}.decode", engine._decode_jit,
                (params_abs, c_abs, slot_vec, slot_vec, scalar),
                donate_min=n_cache_leaves(engine),
                int8_history_len=_SEQ if int8_cache else None,
                int8_head_dim=_HD if (int8_cache and flash) else None,
            ),
            ProgramRecord(
                f"{tag}.scrub", engine._scrub_jit,
                (c_abs, scalar, scalar),
                donate_min=n_cache_leaves(engine),
            ),
        ]

    # paged engines ------------------------------------------------------
    nb = fx.paged_f32.blocks_per_slot
    tables = _sds((_SLOTS, nb), i32)
    table1 = _sds((nb,), i32)
    for tag, engine, int8_cache in (
        ("serve.paged.f32", fx.paged_f32, False),
        ("serve.paged.int8", fx.paged_int8, True),
        ("serve.paged.int8_gather", fx.paged_int8_gather, True),
    ):
        c_abs = cache_abs(engine)
        nleaves = n_cache_leaves(engine)
        flash = engine.decode_kernel == "flash"
        records += [
            ProgramRecord(
                # chunk width 4, deliberately != head_dim (8): with
                # C == hd an einsum-internal [h, s, C] product would be
                # indistinguishable from a [.., s, hd] history tensor
                f"{tag}.prefill_chunk", engine._chunk_jit,
                (p_abs, c_abs, _sds((1, 4), i32), table1, scalar),
                donate_min=nleaves,
                int8_history_len=_SEQ if int8_cache else None,
                int8_head_dim=_HD if (int8_cache and flash) else None,
            ),
            ProgramRecord(
                f"{tag}.decode", engine._decode_jit,
                (p_abs, c_abs, slot_vec, slot_vec, tables, scalar,
                 slot_vec, _sds((_SLOTS,), jnp.bool_), False),
                donate_min=nleaves,
                int8_history_len=_SEQ if int8_cache else None,
                int8_head_dim=_HD if (int8_cache and flash) else None,
            ),
            ProgramRecord(
                f"{tag}.scrub", engine._scrub_jit,
                (c_abs, table1, table1),
                donate_min=nleaves,
            ),
        ]

    # spec: draft/verify/rollback on both layouts ------------------------
    for tag, engine in (
        ("spec.dense", fx.dense_f32), ("spec.paged", fx.paged_f32),
    ):
        spec = SpeculativeDecoder(engine, drafter="truncated",
                                  draft_tokens=2, draft_layers=1)
        c_abs = cache_abs(engine)
        k1 = _sds((_SLOTS, 3), i32)
        paged = engine.kv_layout == "paged"
        verify_args = (p_abs, c_abs, k1, slot_vec, slot_vec) + (
            (tables,) if paged else ()
        )
        rollback_args = (c_abs, slot_vec, slot_vec) + (
            (tables,) if paged else ()
        )
        d_abs = _absify(spec.drafter._dparams)
        draft_args = (d_abs, c_abs, slot_vec, slot_vec) + (
            (tables,) if paged else ()
        )
        records += [
            ProgramRecord(
                f"{tag}.verify", spec._verify_jit, verify_args,
                donate_min=n_cache_leaves(engine),
            ),
            ProgramRecord(
                f"{tag}.rollback", spec._rollback_jit, rollback_args,
                donate_min=n_cache_leaves(engine),
            ),
            ProgramRecord(
                f"{tag}.draft", spec.drafter._jit, draft_args,
                donate_min=n_cache_leaves(engine),
            ),
        ]
    return records


def audit_train_step() -> List[Finding]:
    """Donation + collective signature for the train step, both comm
    paths, traced/lowered on abstract batches (no execution)."""
    from distributeddeeplearning_tpu.parallel import comms
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_size
    from distributeddeeplearning_tpu.train.step import build_train_step

    fx = _train_fixture()
    findings: List[Finding] = []
    n_params = len(jax.tree_util.tree_leaves(fx.state.params))

    # implicit (GSPMD) path ---------------------------------------------
    implicit = build_train_step(fx.mesh, fx.state, compute_dtype=jnp.float32)
    rec = ProgramRecord(
        "train.step.implicit", implicit, (_absify(fx.state), fx.batch_abs),
        donate_min=n_params,
    )
    findings += check_program(rec)
    # its collective signature lives in compiled HLO (GSPMD inserts the
    # gradient all-reduce at compile time); meaningful only on a real
    # multi-shard mesh
    if data_parallel_size(fx.mesh) > 1:
        path, line = rec.location()
        compiled = implicit.lower(_absify(fx.state), fx.batch_abs).compile()
        # mesh-aware: TP all-reduces (tensor-axis replica groups) classify
        # separately, so the gradient-sync check can't be satisfied by —
        # or false-positive on — tensor-parallel traffic
        stats = comms.collective_stats(compiled.as_text(), mesh=fx.mesh)
        if stats.get("all-reduce", {}).get("count", 0) < 1:
            findings.append(
                Finding(
                    "collective-signature", path, line,
                    "train.step.implicit compiled WITHOUT a gradient "
                    f"all-reduce on a {data_parallel_size(fx.mesh)}-shard "
                    f"mesh (collectives: {stats or 'none'})",
                    hint="the implicit path's data-parallel grad sync "
                    "vanished — check the batch/param shardings feeding "
                    "jax.jit",
                )
            )
    else:
        note = (
            "train.step.implicit collective-signature audit (single-"
            "shard mesh — run under an 8-device virtual pod: `ddlt "
            "lint` / `make lint` pin one when no backend is live)"
        )
        _last_skips.append(note)
        logger.warning("program audit SKIPPED: %s", note)

    # explicit comm-overlap path ----------------------------------------
    comm_step = build_train_step(
        fx.mesh, fx.state, compute_dtype=jnp.float32,
        comm_overlap=True, accum_steps=2, bucket_mb=0.25,
    )
    prepared = comm_step.prepare_state(fx.state)
    prep_abs = _absify(prepared)
    rec = ProgramRecord(
        "train.step.comm_overlap", comm_step._jitted,
        (prep_abs, fx.batch_abs), donate_min=n_params,
    )
    traced = comm_step._jitted.trace(prep_abs, fx.batch_abs)
    findings += check_callbacks(rec, traced)
    findings += check_donation(rec, traced)
    path, line = rec.location()
    findings += check_collective_contract(
        traced.jaxpr.jaxpr,
        CollectiveContract(
            in_scan_reduce_scatter_min=comm_step.layout.num_buckets,
        ),
        name="train.step.comm_overlap", path=path, line=line,
    )
    return findings


def run_program_audits() -> List[Finding]:
    _last_skips.clear()
    findings: List[Finding] = []
    for rec in build_program_records():
        findings += check_program(rec)
    findings += audit_train_step()
    findings += check_sharding_coverage()
    return findings
