"""Sharding rules: mesh-aware activation constraints + param partition specs.

Axis convention (launch/mesh.py):
  * ``pod``   — cross-pod data parallelism (multi-pod mesh only),
  * ``data``  — within-pod data parallelism / FSDP weight sharding,
  * ``model`` — tensor parallelism (heads, d_ff, experts, vocab).

Activation constraints are applied through :func:`constrain`, which is a
no-op unless a mesh context has been installed with :func:`use_mesh` — so the
same model code runs in single-device CPU tests and in the 512-chip dry-run.

Param specs come from path-pattern rules; two modes:
  * ``tp``      — tensor parallelism only (small archs; params replicated
                  over data),
  * ``fsdp_tp`` — 2-D sharding (big archs): the non-TP dimension of every
                  matrix is sharded over ``data`` (ZeRO-3 / FSDP behaviour —
                  XLA inserts the per-layer all-gathers).
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_ctx = threading.local()


def _state():
    if not hasattr(_ctx, "mesh"):
        _ctx.mesh = None
        _ctx.batch_axes = ("data",)
    return _ctx


@contextlib.contextmanager
def use_mesh(mesh, batch_axes: Tuple[str, ...] = ("data",)):
    st = _state()
    prev = (st.mesh, st.batch_axes)
    st.mesh, st.batch_axes = mesh, batch_axes
    try:
        yield
    finally:
        st.mesh, st.batch_axes = prev


def active_mesh():
    return _state().mesh


def batch_axes() -> Tuple[str, ...]:
    return _state().batch_axes


def dp_size() -> int:
    """Total extent of the active batch axes (1 if no mesh active)."""
    mesh = active_mesh()
    if mesh is None:
        return 1
    n = 1
    for a in batch_axes():
        if a in mesh.axis_names:
            n *= mesh.devices.shape[mesh.axis_names.index(a)]
    return n


def constrain(x, *spec):
    """with_sharding_constraint(x, P(*spec)) if a mesh is active, else x.

    ``"batch"`` in the spec expands to the active batch axes tuple
    (("pod","data") on the multi-pod mesh; ("pod","data","model") in
    fsdp_pure mode). Any non-batch entry naming an axis already consumed
    by the batch expansion is dropped — e.g. the TP head constraint over
    ``model`` is meaningless when ``model`` carries data parallelism.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    ba = batch_axes()
    used = set(ba)
    expanded = []
    for a in spec:
        if a == "batch":
            if len(ba) == 1:
                expanded.append(ba[0])
            else:
                expanded.append(ba)
        elif a in used:
            expanded.append(None)
        else:
            expanded.append(a)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*expanded)))


def serving_constrain(x, mesh):
    """Shard a serving flush batch over the mesh's ``replica`` axis.

    The serving-mesh analog of the training batch constraint: big flush
    batches data-parallel-shard their rows across replica devices
    (``launch.mesh.make_serving_mesh``). Routed through :func:`constrain`
    on purpose, so serving and training share one constraint path; a
    no-op in values.
    """
    with use_mesh(mesh, batch_axes=("replica",)):
        return constrain(x, "batch")


# ---------------------------------------------------------------------------
# Parameter partition specs
# ---------------------------------------------------------------------------

# (path regex, spec) — first match wins. Specs use axis names or None;
# "fsdp" is replaced by "data" in fsdp_tp mode and None in tp mode.
_RULES: Sequence[Tuple[str, Tuple]] = (
    (r".*(router|conv1d|time_|lora_|rglru)_?.*", ()),  # small: replicate
    (r".*/(s_w|s_in|s_out|scale|gamma|beta|b|w_scale|m_s|v_s)$", ()),
    # Embedding/head: shard ONLY the vocab dim over `model`. Sharding the
    # contracted d dim over `data` (the baseline layout) makes every
    # logits matmul a partial sum -> an all-reduce of the full (B, S, V)
    # f32 logits (24 GB/device/step on codeqwen train_4k, measured);
    # vocab-sharded output needs only (B, S)-sized CE reductions.
    # §Perf iteration A1 — set REPRO_BASELINE_SHARDING=1 for the old rules.
    (r".*embed/w$",            ("model", "fsdp")
     if os.environ.get("REPRO_BASELINE_SHARDING") else ("model", None)),
    (r".*(lm_head|head)/w$",   ("fsdp", "model")
     if os.environ.get("REPRO_BASELINE_SHARDING") else (None, "model")),
    (r".*(wq|wk|wv|wkv|wr|wg|q_up|kv_up|k_rope|x_proj|y_proj|cm_k|cm_r)/w$",
     ("fsdp", "model")),
    (r".*(wo|o_proj|cm_v)/w$", ("model", "fsdp")),     # (H*Dh, d)
    (r".*attn/out/w$",         ("model", "fsdp")),     # RG-LRU out proj
    (r".*kv_down/w$",          ("fsdp", None)),        # MLA: (d, kv_lora)
    (r".*experts/(w_up|w_gate)$", ("model", "fsdp", None)),  # (E, d, ff): EP
    (r".*experts/w_down$",     ("model", None, "fsdp")),     # (E, ff, d)
    (r".*(up|gate)/w$",        ("fsdp", "model")),     # (d, ff)
    (r".*down/w$",             ("model", "fsdp")),     # (ff, d)
)


def spec_for(path: str, shape: Tuple[int, ...], mode: str,
             mesh_shape: dict, *, stacked: bool = False) -> P:
    """Partition spec for one param; falls back to replication, and drops
    any axis assignment that does not divide the dimension evenly.

    Modes:
      * ``tp``        — tensor parallelism only (params replicated over data)
      * ``fsdp_tp``   — 2-D: TP over ``model``, FSDP over ``data``
      * ``fsdp_pure`` — ZeRO-3 over the COMBINED (data, model) axes, no TP:
                        per-layer weight gathers replace activation
                        all-reduces (§Perf iteration A5 — the right regime
                        for <=10B models where weight bytes << activation
                        bytes per layer).

    ``stacked``: param carries a leading scan-over-layers dim (params under
    blocks/enc_blocks) — the rule's spec shifts right by one and the layer
    dim stays unsharded.
    """
    if mode == "fsdp_pure":
        fsdp = ("data", "model")
    elif mode == "fsdp_tp":
        fsdp = "data"
    else:
        fsdp = None

    def axis_size(ax):
        if isinstance(ax, tuple):
            n = 1
            for a in ax:
                n *= mesh_shape.get(a, 1)
            return n
        return mesh_shape.get(ax, 1)

    for pat, spec in _RULES:
        if re.match(pat, path):
            spec = tuple(spec)
            if stacked and spec:
                spec = (None,) + spec
            out = []
            has_fsdp = "fsdp" in spec
            for dim, ax in zip(shape, spec + (None,) * len(shape)):
                if ax == "fsdp":
                    ax = fsdp
                elif ax == "model" and mode == "fsdp_pure":
                    # vocab-style dims (rules with no fsdp element) shard
                    # over the combined axes; TP dims replicate.
                    ax = None if has_fsdp else fsdp
                if ax is not None and dim % axis_size(ax) != 0:
                    ax = None  # indivisible -> replicate this dim
                out.append(ax)
            while out and out[-1] is None:  # P(None) == replicate == P()
                out.pop()
            return P(*out)
    return P()


def param_specs(params, mode: str, mesh) -> "jax.tree_util.PyTreeDef":
    """Pytree of PartitionSpec matching ``params`` (works on ShapeDtypeStruct
    trees too, so the dry-run never materializes parameters).

    int8 deployment params (``w_codes``/``w_gate_codes``) inherit the specs
    of the float weights they replaced (the ``_codes`` suffix is stripped
    before rule matching).
    """
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)

    def one(kp, v):
        path = path_str(kp).replace("_codes", "")
        stacked = path.startswith(("blocks/", "enc_blocks/")) or \
            "/blocks/" in path or "/enc_blocks/" in path or \
            "/mom/blocks/" in path
        return spec_for(path, v.shape, mode, mesh_shape, stacked=stacked)

    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(
        treedef, [one(kp, v) for kp, v in flat])


def named(params_or_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), params_or_specs,
        is_leaf=lambda x: isinstance(x, P))


def zero1_spec(spec: P, shape: Tuple[int, ...], mesh_shape: dict) -> P:
    """ZeRO-1: additionally shard optimizer moments over ``data`` on the
    first dimension that is unsharded and divisible."""
    if "data" in jax.tree_util.tree_leaves(tuple(spec)):
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (dim, ax) in enumerate(zip(shape, parts)):
        if ax is None and dim % mesh_shape.get("data", 1) == 0 and dim > 1:
            parts[i] = "data"
            return P(*parts)
    return spec
