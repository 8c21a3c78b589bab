"""DarkNet-19 under FQ (W2A4): the benchmark's side of a ``darknet`` config.

Four things, each read from the configuration's JSON file:

* ``checkpoint`` -- the seeded stand-in for a trained FQ checkpoint (float
  weights and learned log-scales in the system's parameter layout), made on
  the device in one jitted call from the config's ``weight_seed``;
* ``program`` -- the system under test, built through its normal entry
  points: ``darknet.convert_int`` -> ``darknet.int_serve_fn`` per lane, and
  the serving shape ladder;
* ``reference`` -- the plain model (paper eqs. 1-4, ``bench.refops``): float
  first conv, float pools, entry quantizer, 17 exact integer convs with
  requantization, code pools, decode, float 1x1 head, global average pool;
* ``request_ops`` / ``fq_conv_calls`` -- operations and bytes from shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import refops as R


def _layers(spec):
    return [tuple(l) if isinstance(l, list) else l for l in spec["layers"]]


def _convs(spec):
    return [l for l in _layers(spec) if l != "M"]


def payload_shape(spec, spatial):
    """A request payload with the given spatial dims: (H, W, C)."""
    return tuple(spatial) + (spec["in_channels"],)


def rung_shape(spec):
    return payload_shape(spec, spec["input_hw"])


def checkpoint(spec):
    """Float params and (empty) state of a calibrated FQ DarkNet-19.

    Weights are He-normal; each integer layer's ternary scale puts half of
    its weight codes at +-1, and its output scale maps the 99th percentile
    of its integer sums on a seeded calibration batch to the top code, so
    that codes spread over every level through all 17 layers. The FQ
    hand-off s_in[i+1] == s_out[i] holds by construction."""
    q = spec["quant"]
    layers = _layers(spec)
    n_conv = len(_convs(spec))
    h_in, w_in = spec["input_hw"]
    bits = dict(bits_a=q["bits_a"], bits_w=q["bits_w"])

    @jax.jit
    def make(key):
        k_cal, k_head, *k_conv = jax.random.split(key, 2 + n_conv)
        h = jax.random.normal(k_cal, (2, h_in, w_in, spec["in_channels"]))
        params, codes, s_in, ci, cin = {}, None, None, 0, spec["in_channels"]
        zero = jnp.float32(0.0)
        for layer in layers:
            if layer == "M":
                if codes is None:
                    h = R.maxpool2(h)
                else:
                    codes = R.maxpool2(codes)
                continue
            ks, cout = layer
            w = R.he_normal(k_conv[ci], (ks, ks, cin, cout), ks * ks * cin)
            if ci == 0:
                h = R.edge_conv(h, w, "highest")
                params["conv0"] = {"w": w, "s_w": zero, "s_in": zero,
                                   "s_out": zero}
            else:
                if codes is None:  # entry quantizer of the integer core
                    s_in = jnp.log(jnp.quantile(h, 0.99))
                    codes = R.quantize(h, s_in, bits=q["bits_a"], lo=0.0)
                s_w = R.ternary_scale(w)
                acc = R.int_conv(codes,
                                 R.weight_codes(w, s_w, bits=q["bits_w"]),
                                 padding=[(ks // 2, ks // 2)] * 2)
                s_out = R.out_scale(acc, s_in, s_w, **bits)
                codes = R.requant(
                    acc, R.rescale(s_in, s_w, s_out, bits_out=q["bits_out"],
                                   **bits),
                    n_out=R.n_levels(q["bits_out"]))
                params[f"conv{ci}"] = {"w": w, "s_w": s_w, "s_in": s_in,
                                       "s_out": s_out}
                s_in = s_out
            ci, cin = ci + 1, cout
        w = R.he_normal(k_head, (1, 1, cin, spec["num_classes"]), cin)
        params["head"] = {"w": w, "s_w": zero, "s_in": zero, "s_out": zero}
        return params

    params = make(jax.random.key(spec["weight_seed"]))
    return jax.block_until_ready(params), {}


def model_config(spec):
    from repro.models import darknet
    return darknet.DarkNetConfig(layers=tuple(_layers(spec)),
                                 num_classes=spec["num_classes"],
                                 in_channels=spec["in_channels"])


def quant_config(spec):
    from repro.core.quant import QuantConfig
    q = spec["quant"]
    return QuantConfig(q["bits_w"], q["bits_a"], q["bits_out"], fq=q["fq"])


def program(spec, params, state, devices):
    """The served system: (ladder, one apply fn per lane), each lane over
    its own placed copy of the converted stack when there are several."""
    from repro.core.integer_inference import replicate_stack
    from repro.models import darknet, frontends
    cfg, qcfg = model_config(spec), quant_config(spec)
    ip = darknet.convert_int(params, state, qcfg, cfg,
                             weight_format=spec["weight_format"])
    stacks = [ip] if len(devices) == 1 else replicate_stack(ip, devices)
    ladder = frontends.darknet_serving_ladder(cfg, spec["batcher"]["rungs"])
    return ladder, [darknet.int_serve_fn(s, qcfg, cfg) for s in stacks]


class Reference:
    """The plain model over a checkpoint, its tables recomputed from the
    float params by the paper's formulas; ``edge`` is "highest" for the
    reference and "bf16x3" for the control."""

    BLOCK = 8

    def __init__(self, spec, params, state, edge="highest"):
        q = spec["quant"]
        self.spec, self.edge = spec, edge
        convs = _convs(spec)
        names = [f"conv{i}" for i in range(1, len(convs))]
        bits = dict(bits_a=q["bits_a"], bits_w=q["bits_w"],
                    bits_out=q["bits_out"])
        self.s_entry = params[names[0]]["s_in"]
        self.tables = {
            "w0": params["conv0"]["w"],
            "codes": [R.weight_codes(params[n]["w"], params[n]["s_w"],
                                     bits=q["bits_w"]).astype(jnp.bfloat16)
                      for n in names],
            "scales": [R.rescale(params[n]["s_in"], params[n]["s_w"],
                                 params[n]["s_out"], **bits) for n in names],
            "dec": jnp.exp(params[names[-1]]["s_out"])
            / R.n_levels(q["bits_out"]),
            "head": params["head"]["w"].reshape(convs[-1][1], -1),
        }
        # the checkpoint is closed over, so XLA folds its scalars at compile
        # time, as in the served step
        self._fwd = jax.jit(lambda x: self._forward(self.tables, x))

    def _forward(self, t, x):
        q = self.spec["quant"]
        h, codes, li, first = x, None, 0, True
        for layer in _layers(self.spec):
            if layer == "M":
                if codes is None:
                    h = R.maxpool2(h)
                else:
                    codes = R.maxpool2(codes)
                continue
            ks, _ = layer
            if first:
                h = R.edge_conv(h, t["w0"], self.edge)
                first = False
                continue
            if codes is None:
                codes = R.quantize(h, self.s_entry, bits=q["bits_a"], lo=0.0)
            acc = R.int_conv(codes, t["codes"][li],
                             padding=[(ks // 2, ks // 2)] * 2)
            codes = R.requant(acc, t["scales"][li],
                              n_out=R.n_levels(q["bits_out"]))
            li += 1
        y = R.edge_matmul(codes * t["dec"], t["head"], self.edge)
        return jnp.mean(y, axis=(1, 2))

    def logits(self, payloads):
        """Reference logits of raw payloads (letterboxed here to the rung)."""
        return R.run_blocks(self._fwd, payloads, self.spec["input_hw"],
                            self.BLOCK)


def request_ops(spec):
    """Operations one request needs at the rung: integer core (2 per MAC)
    and float edges (first conv, 1x1 head)."""
    (h, w), cin = spec["input_hw"], spec["in_channels"]
    ops = {"int": 0, "float": 0}
    first = True
    for layer in _layers(spec):
        if layer == "M":
            h, w = h // 2, w // 2
            continue
        ks, cout = layer
        ops["float" if first else "int"] += 2 * h * w * ks * ks * cin * cout
        first, cin = False, cout
    ops["float"] += 2 * h * w * cin * spec["num_classes"]
    return ops


def fq_conv_calls(spec, batch):
    """(ops, bytes) of each fused integer conv call in one step of ``batch``
    requests at the rung. Ops count valid output pixels only; bytes are the
    input codes read, the weights in their stored format and the output
    codes written (the pooled plane where the pool is fused)."""
    layers = _layers(spec)
    (h, w), cin = spec["input_hw"], spec["in_channels"]
    calls, first = [], True
    for i, layer in enumerate(layers):
        if layer == "M":
            h, w = h // 2, w // 2
            continue
        ks, cout = layer
        if not first:
            pooled = i + 1 < len(layers) and layers[i + 1] == "M"
            ho, wo = (h // 2, w // 2) if pooled else (h, w)
            ops = 2 * batch * h * w * ks * ks * cin * cout
            nbytes = (batch * h * w * cin
                      + ks * ks * cin * cout
                      * R.CODE_BYTES[spec["weight_format"]]
                      + batch * ho * wo * cout)
            calls.append((ops, nbytes))
        first, cin = False, cout
    return calls
