"""The paper's keyword-spotting net under FQ (W2A4): the benchmark's side of
a ``kws`` config. Same four parts as ``bench.models.darknet``: seeded
checkpoint, the system built through its entry points, the plain reference
(float embedding + eval BN, entry quantizer, 7 exact dilated integer
conv1d layers, decode, global average pool, float head), and the cost
functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import refops as R

BN_EPS = 1e-5


def payload_shape(spec, spatial):
    """A request payload with the given frame count: (T, n_mfcc)."""
    return tuple(spatial) + (spec["n_mfcc"],)


def rung_shape(spec):
    return payload_shape(spec, [spec["seq_len"]])


def _t_out(spec):
    """Frames after each VALID dilated conv, from the rung."""
    t, out = spec["seq_len"], []
    for d in spec["dilations"]:
        t -= d * (spec["ksize"] - 1)
        out.append(t)
    return out


def checkpoint(spec):
    """Float params and BN state of a calibrated FQ KWS net: He-normal
    weights, BN statistics taken on a seeded calibration batch, and the
    integer layers' scales set as in ``bench.models.darknet.checkpoint``."""
    q = spec["quant"]
    bits = dict(bits_a=q["bits_a"], bits_w=q["bits_w"])
    n_conv = len(spec["dilations"])

    @jax.jit
    def make(key):
        k_cal, k_emb, k_bn, k_head, *k_conv = jax.random.split(key, 4 + n_conv)
        x = jax.random.normal(k_cal, (8, spec["seq_len"], spec["n_mfcc"]))
        ke = jax.random.split(k_emb)
        embed = {"w": R.he_normal(ke[0], (spec["n_mfcc"], spec["embed"]),
                                  spec["n_mfcc"]),
                 "b": 0.1 * jax.random.normal(ke[1], (spec["embed"],))}
        h = R.edge_matmul(x, embed["w"], "highest") + embed["b"]
        kb = jax.random.split(k_bn)
        bn = {"gamma": jax.random.uniform(kb[0], (spec["embed"],),
                                          minval=0.5, maxval=1.5),
              "beta": 0.3 * jax.random.normal(kb[1], (spec["embed"],))}
        bn_state = {"mean": h.mean((0, 1)), "var": h.var((0, 1))}
        h = ((h - bn_state["mean"]) * jax.lax.rsqrt(bn_state["var"] + BN_EPS)
             * bn["gamma"] + bn["beta"])
        s_in = jnp.log(jnp.quantile(h, 0.99))
        codes = R.quantize(h, s_in, bits=q["bits_a"], lo=0.0)
        params = {"embed": embed, "embed_bn": bn}
        cin = spec["embed"]
        for i, d in enumerate(spec["dilations"]):
            ks = spec["ksize"]
            w = R.he_normal(k_conv[i], (ks, cin, spec["filters"]), ks * cin)
            s_w = R.ternary_scale(w)
            acc = R.int_conv(codes, R.weight_codes(w, s_w, bits=q["bits_w"]),
                             padding="VALID", dilation=d)
            s_out = R.out_scale(acc, s_in, s_w, **bits)
            codes = R.requant(
                acc, R.rescale(s_in, s_w, s_out, bits_out=q["bits_out"],
                               **bits),
                n_out=R.n_levels(q["bits_out"]))
            params[f"conv{i}"] = {"w": w, "s_w": s_w, "s_in": s_in,
                                  "s_out": s_out}
            s_in, cin = s_out, spec["filters"]
        kh = jax.random.split(k_head)
        params["head"] = {
            "w": R.he_normal(kh[0], (cin, spec["num_classes"]), cin),
            "b": 0.1 * jax.random.normal(kh[1], (spec["num_classes"],))}
        return params, {"embed_bn": bn_state}

    out = make(jax.random.key(spec["weight_seed"]))
    return jax.block_until_ready(out)


def model_config(spec):
    from repro.models import kws
    return kws.KWSConfig(n_mfcc=spec["n_mfcc"], embed=spec["embed"],
                         filters=spec["filters"], ksize=spec["ksize"],
                         dilations=tuple(spec["dilations"]),
                         num_classes=spec["num_classes"],
                         seq_len=spec["seq_len"])


def quant_config(spec):
    from repro.core.quant import QuantConfig
    q = spec["quant"]
    return QuantConfig(q["bits_w"], q["bits_a"], q["bits_out"], fq=q["fq"])


def program(spec, params, state, devices):
    """The served system: (ladder, one apply fn per lane)."""
    from repro.core.integer_inference import replicate_stack
    from repro.models import frontends, kws
    cfg, qcfg = model_config(spec), quant_config(spec)
    ip = kws.convert_int(params, state, qcfg, cfg,
                         weight_format=spec["weight_format"])
    stacks = [ip] if len(devices) == 1 else replicate_stack(ip, devices)
    ladder = frontends.kws_serving_ladder(cfg, spec["batcher"]["rungs"])
    return ladder, [kws.int_serve_fn(s, qcfg, cfg) for s in stacks]


class Reference:
    """The plain KWS net over a checkpoint; see darknet.Reference."""

    BLOCK = 64

    def __init__(self, spec, params, state, edge="highest"):
        q = spec["quant"]
        self.spec, self.edge = spec, edge
        names = [f"conv{i}" for i in range(len(spec["dilations"]))]
        self.s_entry = params[names[0]]["s_in"]
        bits = dict(bits_a=q["bits_a"], bits_w=q["bits_w"],
                    bits_out=q["bits_out"])
        self.tables = {
            "embed": params["embed"], "bn": params["embed_bn"],
            "bn_state": state["embed_bn"],
            "codes": [R.weight_codes(params[n]["w"], params[n]["s_w"],
                                     bits=q["bits_w"]).astype(jnp.bfloat16)
                      for n in names],
            "scales": [R.rescale(params[n]["s_in"], params[n]["s_w"],
                                 params[n]["s_out"], **bits) for n in names],
            "dec": jnp.exp(params[names[-1]]["s_out"])
            / R.n_levels(q["bits_out"]),
            "head": params["head"],
        }
        # the checkpoint is closed over, so XLA folds its scalars at compile
        # time, as in the served step
        self._fwd = jax.jit(lambda x: self._forward(self.tables, x))

    def _forward(self, t, x):
        q = self.spec["quant"]
        h = R.edge_matmul(x, t["embed"]["w"], self.edge) + t["embed"]["b"]
        st = t["bn_state"]
        h = ((h - st["mean"]) * jax.lax.rsqrt(st["var"] + BN_EPS)
             * t["bn"]["gamma"] + t["bn"]["beta"])
        codes = R.quantize(h, self.s_entry, bits=q["bits_a"], lo=0.0)
        for wc, sc, d in zip(t["codes"], t["scales"], self.spec["dilations"]):
            acc = R.int_conv(codes, wc, padding="VALID", dilation=d)
            codes = R.requant(acc, sc, n_out=R.n_levels(q["bits_out"]))
        h = jnp.mean(codes * t["dec"], axis=1)
        return R.edge_matmul(h, t["head"]["w"], self.edge) + t["head"]["b"]

    def logits(self, payloads):
        """Reference logits of raw payloads (letterboxed here to the rung)."""
        return R.run_blocks(self._fwd, payloads, [self.spec["seq_len"]],
                            self.BLOCK)


def request_ops(spec):
    """Operations one request needs at the rung: integer conv core and float
    edges (embedding, head)."""
    t, ks = spec["seq_len"], spec["ksize"]
    ops = {"float": 2 * t * spec["n_mfcc"] * spec["embed"]
           + 2 * spec["filters"] * spec["num_classes"], "int": 0}
    cin = spec["embed"]
    for t_out in _t_out(spec):
        ops["int"] += 2 * t_out * ks * cin * spec["filters"]
        cin = spec["filters"]
    return ops


def fq_conv_calls(spec, batch):
    """(ops, bytes) of each fused integer conv call in one step of ``batch``
    requests at the rung (see darknet.fq_conv_calls)."""
    ks, cout = spec["ksize"], spec["filters"]
    t_in, cin, calls = spec["seq_len"], spec["embed"], []
    for t_out in _t_out(spec):
        calls.append((2 * batch * t_out * ks * cin * cout,
                      batch * t_in * cin
                      + ks * cin * cout * R.CODE_BYTES[spec["weight_format"]]
                      + batch * t_out * cout))
        t_in, cin = t_out, cout
    return calls
