"""Integer-only inference (paper eq. 4 + §3.4 deployment story).

After FQ training, the float scale parameters are only needed to *place the
bins*: a trained FQ layer collapses to

    int8 weight codes  +  one folded rescale scalar per layer,

and the whole conv stack runs integer-in / integer-out on the fq_matmul
Pallas kernel. Only the final layer's  e^s / n  escapes to float, to feed the
full-precision global-average-pool + softmax (paper §3.4, last paragraph).

The deployment artifact is a :class:`ConvertedStack`: per-layer codes +
rescales + quantizer ranges, plus the float-side extras (FP edge layers,
entry quantizer, final decode scale). It is mapping-compatible with the
per-layer dicts it replaced (``stack["conv0"]`` still works), is a
registered jax pytree, and carries an explicit back-map —
:meth:`ConvertedStack.rederive` turns *updated* float weights back into
re-derived codes/rescales, which is what deployment-in-the-loop retraining
(core/deploy_qat.py) converges around: train floats, rederive, redeploy.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from . import quant
from .noise import NoiseConfig, derive_seed, perturb_codes
from .quant import (QuantConfig, RELU_BOUND, WEIGHT_BOUND, n_levels,
                    quantize_to_int)


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def _validate_layer(p, out, name: Optional[str]):
    """Conversion-time range validation (raise, don't silently clip).

    ``quantize_to_int`` clips finite weights into range by construction, so
    out-of-range or garbage codes can only come from non-finite params
    (NaN/inf weights or scales) — which previously cast to int8 silently.
    Skipped under tracing (the QAT forward converts inside jit, where the
    eager conversion that produced the stack already validated).
    """
    tag = f"convert_layer({name or 'layer'})"
    for k in ("s_in", "s_w", "s_out"):
        if _is_concrete(p[k]) and not np.isfinite(np.asarray(p[k])).all():
            raise ValueError(f"{tag}: non-finite scale param {k!r}")
    if _is_concrete(p["w"]) and not np.isfinite(np.asarray(p["w"])).all():
        raise ValueError(f"{tag}: non-finite weights (quantize_to_int would "
                         "cast NaN/inf to garbage int8 codes)")
    codes = out["w_codes"]
    if _is_concrete(codes):
        # packed codes are decoded first; the zero pad lanes are in range
        c = np.asarray(quant.unpack_codes(
            codes, out.get("weight_format", "int8")), dtype=np.int32)
        if c.min() < -out["n_w"] or c.max() > out["n_w"]:
            raise ValueError(
                f"{tag}: weight codes [{c.min()}, {c.max()}] outside the "
                f"recorded quantizer range [-{out['n_w']}, {out['n_w']}]")
    scalar = out["alpha"] if "alpha" in out else out["rescale"]
    if _is_concrete(scalar):
        s = float(np.asarray(scalar))
        if not np.isfinite(s) or s <= 0.0:
            raise ValueError(f"{tag}: folded epilogue scalar is {s!r} "
                             "(expected finite and > 0)")


def convert_layer(p, qcfg: QuantConfig, *, relu_out: bool = True,
                  final: bool = False, validate: bool = True,
                  name: Optional[str] = None, weight_format: str = "int8"):
    """Trained FQ layer params -> integer deployment params.

    Returns a dict with ``w_codes`` plus the folded epilogue scalar:
    ``rescale`` (inner layers) or ``alpha`` (final layer, dequant epilogue).
    ``weight_format`` selects weight-code storage: "int8" keeps the im2col
    int8 layout; "int4"/"ternary" pack 2/4 codes per byte (per-tap channel
    padding for conv weights — see core.quant). A format whose quantizer
    range cannot hold bits_w codes raises (never silently clip a trained
    code into a smaller declared range); this check is static, so it also
    fires under tracing. ``validate`` checks the produced codes against
    the recorded quantizer ranges and the folded scalar for finiteness,
    raising a clear error instead of deploying silently-clipped garbage.
    """
    assert qcfg.fq and qcfg.bits_out is not None and qcfg.bits_w is not None
    if weight_format not in quant.WEIGHT_FORMATS:
        raise ValueError(
            f"convert_layer({name or 'layer'}): unknown weight_format "
            f"{weight_format!r}; expected one of {quant.WEIGHT_FORMATS}")
    if quant.format_range(weight_format) < n_levels(qcfg.bits_w):
        raise ValueError(
            f"convert_layer({name or 'layer'}): weight_format="
            f"{weight_format!r} holds codes in ±{quant.format_range(weight_format)} "
            f"but bits_w={qcfg.bits_w} trains codes in "
            f"±{n_levels(qcfg.bits_w)} — refusing to clip")
    w_codes = quantize_to_int(p["w"], p["s_w"], bits=qcfg.bits_w,
                              b=WEIGHT_BOUND)
    flat = w_codes.reshape(-1, w_codes.shape[-1])  # im2col layout
    if weight_format == "int8":
        stored = flat
    elif w_codes.ndim >= 3:
        # conv weights: (taps..., cin, cout) — pad cin per tap so every
        # tap owns whole byte rows (the fused kernel's read granularity)
        taps = int(np.prod(w_codes.shape[:-2]))
        stored = quant.pack_im2col_codes(flat, taps, weight_format)
    else:
        stored = quant.pack_codes(flat, weight_format)
    out = {
        "w_codes": stored,
        "weight_format": weight_format,
        "n_out": n_levels(qcfg.bits_out),
        "lo": 0 if relu_out else -n_levels(qcfg.bits_out),
        "s_out": p["s_out"],
        # quantizer ranges for the code-domain noise model (§4.4): weight
        # codes live in [-n_w, n_w], input activation codes in [0, n_a]
        # (the integer stacks are quantized-ReLU stacks).
        "n_w": n_levels(qcfg.bits_w),
        "n_a": n_levels(qcfg.bits_a if qcfg.bits_a is not None
                        else qcfg.bits_out),
    }
    if final:
        out["alpha"] = ops.fold_alpha(
            p["s_in"], p["s_w"], bits_a=qcfg.bits_a, bits_w=qcfg.bits_w
        )
    else:
        out["rescale"] = ops.fold_rescale(
            p["s_in"], p["s_w"], p["s_out"],
            bits_a=qcfg.bits_a, bits_w=qcfg.bits_w, bits_out=qcfg.bits_out,
        )
    if validate:
        _validate_layer(p, out, name)
    return out


# ---------------------------------------------------------------------------
# ConvertedStack: the deployment artifact + its back-map
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Static per-layer conversion recipe (aux data of the stack pytree).

    ``weight_format`` is part of the recipe: ``rederive`` re-packs with
    the same format, so a packed stack round-trips bit-exactly.
    """
    name: str
    relu_out: bool = True
    final: bool = False
    weight_format: str = "int8"


class ConvertedStack:
    """Per-layer integer deployment params + float-side extras, one artifact.

    * ``layers``: {name: converted dict} from :func:`convert_layer` —
      codes, folded rescale/alpha, quantizer ranges.
    * ``extras``: everything the integer core does not own (FP edge layers,
      ``entry`` quantizer scale, ``s_out_last`` decode scale, BN tuples).
    * ``specs``/``qcfg``: the static conversion recipe, so the stack can
      re-derive itself from updated float weights (:meth:`rederive`) —
      the train -> convert -> serve round-trip's back-map.

    Mapping-compatible with the per-layer dict bundles it replaced:
    ``stack["conv0"]`` resolves layers first, then extras.
    """

    def __init__(self, qcfg: QuantConfig, specs: Sequence[LayerSpec],
                 layers: Dict[str, dict], extras: Dict[str, Any],
                 handoff_edges: Optional[Sequence[Tuple[str, str, str, str]]]
                 = None):
        self.qcfg = qcfg
        self.specs = tuple(specs)
        self.layers = dict(layers)
        self.extras = dict(extras)
        # None -> linear chain (pairwise over specs); a tuple of
        # (src, src_field, dst, dst_field) edges -> residual-add DAG
        # hand-off (requant-to-common-scale ties), checked by rederive.
        self.handoff_edges = (None if handoff_edges is None
                              else tuple(tuple(e) for e in handoff_edges))

    # -- mapping compatibility ---------------------------------------------

    def __getitem__(self, key: str):
        if key in self.layers:
            return self.layers[key]
        return self.extras[key]

    def __contains__(self, key: str) -> bool:
        return key in self.layers or key in self.extras

    def keys(self):
        return list(self.layers) + list(self.extras)

    def __iter__(self):
        return iter(self.keys())

    def __len__(self) -> int:
        return len(self.layers) + len(self.extras)

    def items(self):
        return [(k, self[k]) for k in self.keys()]

    @property
    def layer_names(self) -> Tuple[str, ...]:
        return tuple(s.name for s in self.specs)

    # -- the back-map -------------------------------------------------------

    def rederive(self, layer_params: Dict[str, dict], *, extras=None,
                 check_handoff: bool = True) -> "ConvertedStack":
        """Updated float layer params -> a freshly converted stack.

        The explicit back-map of the round-trip pipeline: after a
        deploy-QAT finetune moves the float weights, ``rederive`` re-runs
        the SAME conversion recipe (specs + qcfg) over the new params.
        Re-deriving from unchanged params is idempotent (bit-exact codes
        and rescales).

        Extras that are pure functions of the layer params — the
        ``entry`` quantizer scale (first layer's s_in) and the
        ``s_out_last`` decode scale — are RE-DERIVED too: the last
        layer's new rescale targets its new s_out, so decoding with a
        stale s_out_last would mis-scale every output. ``extras=None``
        keeps the remaining extras (FP edge layers); pass rebuilt extras
        when those retrained as well (models' ``int_extras``).
        """
        if check_handoff:
            if self.handoff_edges is not None:
                _check_handoff_edges(layer_params, self.handoff_edges)
            else:
                _check_handoff(layer_params, self.specs)
        layers = {
            s.name: convert_layer(layer_params[s.name], self.qcfg,
                                  relu_out=s.relu_out, final=s.final,
                                  name=s.name,
                                  weight_format=s.weight_format)
            for s in self.specs
        }
        extras = dict(self.extras if extras is None else extras)
        if "entry" in extras:
            extras["entry"] = {"s_in": layer_params[self.specs[0].name]["s_in"]}
        if "s_out_last" in extras:
            extras["s_out_last"] = layer_params[self.specs[-1].name]["s_out"]
        return ConvertedStack(self.qcfg, self.specs, layers, extras,
                              handoff_edges=self.handoff_edges)


# Python-int/str fields of a converted layer (kernel grid / epilogue /
# dispatch statics). They flatten into pytree AUX data, not leaves, so a
# ConvertedStack can cross a jit boundary as an argument without tracing
# n_out/lo/weight_format into the kernels' static parameters.
_STATIC_LAYER_KEYS = ("n_out", "lo", "n_w", "n_a", "weight_format")


def _stack_flatten(s: ConvertedStack):
    dyn = {n: {k: v for k, v in d.items() if k not in _STATIC_LAYER_KEYS}
           for n, d in s.layers.items()}
    static = tuple(sorted(
        (n, tuple(sorted((k, d[k]) for k in _STATIC_LAYER_KEYS if k in d)))
        for n, d in s.layers.items()))
    return (dyn, s.extras), (s.qcfg, s.specs, static, s.handoff_edges)


def _stack_unflatten(aux, children):
    qcfg, specs, static, edges = aux
    dyn, extras = children
    layers = {n: dict(d) for n, d in dyn.items()}
    for n, kv in static:
        layers[n].update(dict(kv))
    return ConvertedStack(qcfg, specs, layers, extras, handoff_edges=edges)


jax.tree_util.register_pytree_node(ConvertedStack, _stack_flatten,
                                   _stack_unflatten)


def place_stack(stack: ConvertedStack, device) -> ConvertedStack:
    """Copy a ConvertedStack's arrays onto ``device``.

    The kernel statics (n_out/lo/n_w/n_a/weight_format) ride in pytree
    AUX data, so ``jax.device_put`` moves only the code/scale leaves and
    the reconstructed stack serves identically — ``stack_digest`` is
    placement-invariant."""
    return jax.device_put(stack, device)


def replicate_stack(stack: ConvertedStack, devices) -> list:
    """One placed copy of ``stack`` per device (serving-mesh replicas).

    On an oversubscribed CPU host (``launch.mesh.replica_devices`` with
    one physical device) the copies share buffers — which IS the
    CPU-simulation semantics: logically distinct replicas, one backing
    store."""
    return [place_stack(stack, d) for d in devices]


def _check_handoff(layer_params: Dict[str, dict], specs: Sequence[LayerSpec],
                   *, atol: float = 1e-6):
    """Validate the FQ hand-off contract s_in[i+1] == s_out[i].

    The integer path hands CODES layer-to-layer, which is only meaningful
    when consecutive quantizers share bin edges; a violated contract used
    to produce silently-wrong rescales. Skipped for traced params.
    """
    for a, b in zip(specs, specs[1:]):
        s_out = layer_params[a.name]["s_out"]
        s_in = layer_params[b.name]["s_in"]
        if not (_is_concrete(s_out) and _is_concrete(s_in)):
            continue
        if not np.allclose(np.asarray(s_in), np.asarray(s_out), atol=atol):
            raise ValueError(
                f"FQ hand-off contract violated between {a.name!r} and "
                f"{b.name!r}: s_in={float(np.asarray(s_in)):.6f} != "
                f"s_out={float(np.asarray(s_out)):.6f}. Run "
                "integer_inference.sync_handoff(params, names) first "
                "(independently-trained scales must be tied before the "
                "codes can hand over).")


def _check_handoff_edges(layer_params: Dict[str, dict],
                         edges: Sequence[Tuple[str, str, str, str]],
                         *, atol: float = 1e-6):
    """Validate the FQ hand-off contract over an explicit scale-tie edge
    list — the chain contract extended to residual-add DAGs.

    Each edge ``(src, src_field, dst, dst_field)`` asserts the two stored
    scales are equal. For a residual add this is the requant-to-common-
    scale condition: every branch rejoining the stream must requantize
    onto the stream scale, else code addition mixes incompatible bins.
    Skipped per-edge for traced params (mirrors ``_check_handoff``).
    """
    for src, sf, dst, df in edges:
        s_src = layer_params[src][sf]
        s_dst = layer_params[dst][df]
        if not (_is_concrete(s_src) and _is_concrete(s_dst)):
            continue
        if not np.allclose(np.asarray(s_dst), np.asarray(s_src), atol=atol):
            raise ValueError(
                f"FQ hand-off contract violated on edge {src}.{sf} -> "
                f"{dst}.{df}: {float(np.asarray(s_dst)):.6f} != "
                f"{float(np.asarray(s_src)):.6f}. Run "
                "integer_inference.sync_handoff_edges(params, edges) first.")


def sync_handoff_edges(params: Dict[str, dict],
                       edges: Sequence[Tuple[str, str, str, str]]):
    """Enforce a DAG hand-off: copy ``src.src_field -> dst.dst_field`` for
    every edge, in order, functionally (the input is never mutated).

    The DAG generalization of :func:`sync_handoff`: edges are applied in
    list order, so ties rooted at one canonical scale (e.g. a residual
    stream's scale) propagate through the whole graph in one pass when the
    edge list is topologically ordered (models emit them that way).
    """
    new = dict(params)
    for src, sf, dst, df in edges:
        new[dst] = {**new[dst], df: new[src][sf]}
    return new


def sync_handoff(params: Dict[str, dict], names: Sequence[str]):
    """Enforce s_in[i+1] = s_out[i] along a layer chain, functionally.

    Deploy-QAT training ties the scales structurally (layer i's surrogate
    reads layer i-1's s_out), leaving the stored s_in of inner layers
    stale; call this before converting. Returns a new params dict — the
    input (possibly a cached stand-in) is never mutated.
    """
    new = dict(params)
    for a, b in zip(names, names[1:]):
        new[b] = {**new[b], "s_in": new[a]["s_out"]}
    return new


def convert_stack(layer_params: Dict[str, dict], qcfg: QuantConfig, *,
                  specs: Sequence[LayerSpec], extras: Dict[str, Any],
                  check_handoff: bool = True,
                  weight_format: Optional[str] = None,
                  handoff_edges: Optional[Sequence[Tuple[str, str, str, str]]]
                  = None) -> ConvertedStack:
    """Convert an ordered chain (or DAG) of trained FQ layers into a
    ConvertedStack.

    ``weight_format`` overrides every spec's storage format: an explicit
    format name, or "auto" for the densest format that holds bits_w codes
    (ternary nets pack 4 codes/byte). The resolved format is recorded on
    the specs, so ``rederive`` re-packs identically. ``None`` keeps each
    spec's own (default int8) format.

    ``handoff_edges`` replaces the pairwise chain hand-off check with an
    explicit scale-tie edge list — residual-add DAGs (the transformer
    stream) declare their requant-to-common-scale ties here. The edges
    are recorded on the stack so ``rederive`` re-validates the same DAG.
    """
    specs = tuple(specs)
    if weight_format is not None:
        fmt = (quant.auto_weight_format(n_levels(qcfg.bits_w))
               if weight_format == "auto" else weight_format)
        specs = tuple(dataclasses.replace(s, weight_format=fmt)
                      for s in specs)
    if check_handoff:
        if handoff_edges is not None:
            _check_handoff_edges(layer_params, handoff_edges)
        else:
            _check_handoff(layer_params, specs)
    layers = {
        s.name: convert_layer(layer_params[s.name], qcfg,
                              relu_out=s.relu_out, final=s.final, name=s.name,
                              weight_format=s.weight_format)
        for s in specs
    }
    return ConvertedStack(qcfg, specs, layers, extras,
                          handoff_edges=handoff_edges)


def stack_digest(stack: ConvertedStack) -> str:
    """Short content digest of a deployment artifact.

    Covers the full serving identity: the conversion recipe (qcfg label +
    specs), every layer's arrays and static aux, and every extras leaf —
    two stacks digest equal iff they serve bit-identically. The fleet
    control plane records it at register/swap time so an incident replay
    can prove the rebuilt (or retrained) stack matches the recorded one
    before comparing any outputs.
    """
    import hashlib
    h = hashlib.blake2s(digest_size=10)
    h.update(stack.qcfg.label().encode())
    for s in stack.specs:
        h.update(f"{s.name}:{int(s.relu_out)}:{int(s.final)}"
                 f":{s.weight_format}".encode())
    if stack.handoff_edges is not None:
        # DAG stacks fold their scale-tie topology in; chain stacks
        # (edges None) hash exactly as before, so recorded fleet digests
        # stay valid.
        for e in stack.handoff_edges:
            h.update(":".join(e).encode())

    def leaf(x):
        if isinstance(x, (int, float, bool)):
            h.update(repr(x).encode())
        else:
            a = np.ascontiguousarray(np.asarray(x))
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())

    def walk(x):
        if isinstance(x, dict):
            for k in sorted(x):
                h.update(str(k).encode())
                walk(x[k])
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        else:
            leaf(x)

    for name in stack.layer_names:
        h.update(name.encode())
        walk(stack.layers[name])
    walk(stack.extras)
    return h.hexdigest()


def entry_codes(x, p, qcfg: QuantConfig, *, b_in: float = RELU_BOUND):
    """Quantize a float tensor entering the integer stack to int8 codes."""
    return ops.quantize_to_codes(x, p["s_in"], bits=qcfg.bits_a, b=b_in)


def noisy_operands(ip, codes, noise: Optional[NoiseConfig], rng, *,
                   a_lo: int = 0):
    """Apply the paper's §4.4 noise model at the integer-layer boundary.

    Returns ``(w_codes, a_codes, mac_sigma_acc, mac_seed)``:

      * weight codes perturbed in code units (memory-cell noise, clipped
        to the weight quantizer range [-n_w, n_w]),
      * input activation codes perturbed in code units (DAC noise,
        clipped to [a_lo, n_a] — one draw per layer input, mirroring the
        float path's per-conv input-quantizer noise; ReLU stacks keep
        the default a_lo=0, signed transformer stream codes pass
        a_lo=-n_a),
      * the ADC noise std folded into ACCUMULATOR units for the kernel
        epilogue: sigma_mac is a fraction of the OUTPUT quantizer's LSB
        and requant maps accumulator -> output codes by ``rescale``, so
        sigma_acc = sigma_mac / rescale,
      * a uint32 seed split off ``rng`` for the kernel's deterministic
        noise field.

    With ``noise`` disabled (None or all-zero sigmas) or no ``rng``,
    returns the operands untouched and ``(None, None)`` — the clean path
    stays bit-exact and compiles the clean kernel.
    """
    if noise is None or not noise.enabled or rng is None:
        return ip["w_codes"], codes, None, None
    k_w, k_a, k_mac = jax.random.split(rng, 3)
    n_w = ip.get("n_w", 127)
    # Incoming codes are [0, n_a] at the entry layer (bits_a quantizer)
    # but [0, n_out] codes handed over from the previous layer everywhere
    # else; the DAC range must cover BOTH, else a bits_a < bits_out config
    # would have the noise clip destroy valid codes.
    a_hi = max(ip.get("n_a", 127), ip.get("n_out", 127))
    fmt = ip.get("weight_format", "int8")
    w_codes = ip["w_codes"]
    if fmt != "int8":
        # memory-cell noise perturbs CODES, not storage bytes: unpack,
        # perturb, re-pack. The perturbed pad lanes stay inert (their
        # activation lanes are zero / sliced away on both impls).
        w_codes = quant.unpack_codes(w_codes, fmt)
    w_codes = perturb_codes(w_codes, k_w, noise.sigma_w,
                            lo=-n_w, hi=n_w)
    if fmt != "int8":
        w_codes = quant.pack_codes(w_codes, fmt)
    a_codes = perturb_codes(codes, k_a, noise.sigma_a, lo=a_lo, hi=a_hi)
    if noise.sigma_mac > 0:
        return (w_codes, a_codes, noise.sigma_mac / ip["rescale"],
                derive_seed(k_mac))
    return w_codes, a_codes, None, None


def int_linear(ip, codes, *, noise: Optional[NoiseConfig] = None, rng=None,
               mac_chunks: int = 1, a_lo: int = 0):
    w_codes, codes, sig, seed = noisy_operands(ip, codes, noise, rng,
                                               a_lo=a_lo)
    return ops.int_matmul(codes, w_codes, ip["rescale"],
                          epilogue="requant", n_out=ip["n_out"], lo=ip["lo"],
                          noise_sigma_acc=sig, noise_seed=seed,
                          mac_chunks=mac_chunks,
                          weight_format=ip.get("weight_format", "int8"))


def int_residual_add(a_codes, b_codes, *, n_out: int, lo: Optional[int] = None):
    """Code-domain residual add at a COMMON scale.

    Both operands must be codes under the SAME output quantizer (scale
    e^s, denominator n_out) — that is exactly what the requant-to-common-
    scale hand-off edges of a residual DAG guarantee. The add is then a
    saturating integer add: widen to int32 (int8-native adds would wrap
    at +/-254 and trip the absint signed-wrap check), clip to the
    quantizer range, and narrow back to int8 codes.
    """
    lo = -n_out if lo is None else lo
    acc = a_codes.astype(jnp.int32) + b_codes.astype(jnp.int32)
    return jnp.clip(acc, lo, n_out).astype(jnp.int8)


def int_linear_final(ip, codes):
    return ops.int_matmul(codes, ip["w_codes"], ip["alpha"],
                          epilogue="dequant",
                          weight_format=ip.get("weight_format", "int8"))


def int_conv1d(ip, codes, *, ksize: int, dilation: int = 1, impl=None,
               noise: Optional[NoiseConfig] = None, rng=None,
               mac_chunks: int = 1):
    w_codes, codes, sig, seed = noisy_operands(ip, codes, noise, rng)
    return ops.fq_conv1d_int(codes, w_codes, ip["rescale"],
                             ksize=ksize, dilation=dilation,
                             n_out=ip["n_out"], lo=ip["lo"], impl=impl,
                             noise_sigma_acc=sig, noise_seed=seed,
                             mac_chunks=mac_chunks,
                             weight_format=ip.get("weight_format", "int8"))


def int_conv2d(ip, codes, *, ksize: int, stride: int = 1, padding: int = 0,
               dilation: int = 1, impl=None,
               noise: Optional[NoiseConfig] = None, rng=None,
               mac_chunks: int = 1):
    w_codes, codes, sig, seed = noisy_operands(ip, codes, noise, rng)
    return ops.fq_conv2d_int(codes, w_codes, ip["rescale"],
                             ksize=ksize, stride=stride, padding=padding,
                             dilation=dilation,
                             n_out=ip["n_out"], lo=ip["lo"], impl=impl,
                             noise_sigma_acc=sig, noise_seed=seed,
                             mac_chunks=mac_chunks,
                             weight_format=ip.get("weight_format", "int8"))


def int_conv1d_final(ip, codes, *, ksize: int, dilation: int = 1, impl=None):
    return ops.fq_conv1d_int(codes, ip["w_codes"], ip["alpha"],
                             ksize=ksize, dilation=dilation,
                             epilogue="dequant", impl=impl,
                             weight_format=ip.get("weight_format", "int8"))


def int_conv2d_final(ip, codes, *, ksize: int, stride: int = 1,
                     padding: int = 0, dilation: int = 1, impl=None):
    return ops.fq_conv2d_int(codes, ip["w_codes"], ip["alpha"],
                             ksize=ksize, stride=stride, padding=padding,
                             dilation=dilation, epilogue="dequant", impl=impl,
                             weight_format=ip.get("weight_format", "int8"))


def int_conv2d_pool(ip, codes, *, ksize: int, stride: int = 1,
                    padding: int = 0, dilation: int = 1, pool: int = 2,
                    impl=None, noise: Optional[NoiseConfig] = None, rng=None,
                    mac_chunks: int = 1):
    """Conv + non-overlapping maxpool as ONE integer op (conv+pool pairs).

    Behind the kernels/ops dispatch point: on the fused path the maxpool
    runs on the int32 accumulator inside the conv kernel's VMEM epilogue —
    the unpooled activation plane never reaches HBM; the im2col path keeps
    the unfused conv + code-domain pool composition as the parity oracle.
    ADC noise perturbs the PRE-POOL accumulator on both paths (max
    commutes with requant, so they stay bit-identical).
    """
    w_codes, codes, sig, seed = noisy_operands(ip, codes, noise, rng)
    return ops.fq_conv2d_pool_int(codes, w_codes, ip["rescale"],
                                  ksize=ksize, stride=stride, padding=padding,
                                  dilation=dilation, pool=pool,
                                  n_out=ip["n_out"], lo=ip["lo"], impl=impl,
                                  noise_sigma_acc=sig, noise_seed=seed,
                                  mac_chunks=mac_chunks,
                                  weight_format=ip.get("weight_format",
                                                       "int8"))


def int_conv2d_add(ip, codes, shortcut, *, ksize: int, stride: int = 1,
                   padding: int = 0, dilation: int = 1, impl=None,
                   noise: Optional[NoiseConfig] = None, rng=None,
                   mac_chunks: int = 1):
    """A residual block's last conv, its add and the ReLU after it as ONE
    integer op: ``clip(branch + shortcut, 0, n_out)``, where ``branch``
    is the conv's signed requant (``ip["lo"]`` = -n_out) onto the stream
    scale that ``shortcut`` is already on (the DAG's hand-off edges tie
    them).

    Behind the kernels/ops dispatch point, as ``int_conv2d_pool``: the
    fused path adds in the conv kernel's VMEM epilogue, the im2col path
    composes the conv with :func:`int_residual_add` (the parity oracle).
    ADC noise perturbs the conv's accumulator before the add on both
    paths; the shortcut's codes are taken as they are.
    """
    w_codes, codes, sig, seed = noisy_operands(ip, codes, noise, rng)
    return ops.fq_conv2d_add_int(codes, w_codes, ip["rescale"], shortcut,
                                 ksize=ksize, stride=stride, padding=padding,
                                 dilation=dilation, n_out=ip["n_out"],
                                 lo=ip["lo"], impl=impl,
                                 noise_sigma_acc=sig, noise_seed=seed,
                                 mac_chunks=mac_chunks,
                                 weight_format=ip.get("weight_format",
                                                      "int8"))


def int_maxpool2d(codes, *, window: int = 2, stride: int = 2):
    """2x2 maxpool directly on int8 codes (NHWC).

    Valid because the learned quantizer is monotone: Q(max(x)) == max(Q(x)),
    so pooling commutes with requantization and the codes never need to be
    decoded to float for the pool (paper §3.4's integer-only stack).
    Prefer ``int_conv2d_pool`` when the pool directly follows a conv — it
    fuses the pool into the conv epilogue and skips this HBM round-trip.
    """
    return ops.maxpool2d(codes, window=window, stride=stride)


def decode_output(codes_or_float, s_out, bits_out: Optional[int]):
    """Final-layer codes -> real values: e^s / n * codes (paper §3.4)."""
    if bits_out is None:
        return codes_or_float
    return jnp.exp(s_out) / n_levels(bits_out) * codes_or_float.astype(jnp.float32)
