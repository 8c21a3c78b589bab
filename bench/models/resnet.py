"""ResNet-50 v1.5 (and the paper's basic-block nets) under FQ (W2A4): the
benchmark's side of a ``resnet`` config. Same parts as
``bench.models.darknet``:

* ``checkpoint`` -- seeded stand-in for a trained FQ checkpoint in the
  system's parameter layout, calibrated on a seeded batch so that codes
  spread over the levels at every conv and every block output;
* ``program`` -- the system under test through its entry points:
  ``resnet.convert_int`` -> ``resnet.int_serve_fn`` per lane, and the
  serving shape ladder;
* ``Reference`` -- the plain model (paper eqs. 1-4, ``bench.refops``):
  float stem conv, float 3x3/2 max pool, entry quantizer, exact integer
  convs with requantization, the integer residual add with its two clips,
  decode, global average pool, float classifier;
* ``request_ops`` / ``fq_conv_calls`` / ``fq_conv_add_calls`` --
  operations and bytes from shapes.

Every conv and pool pads by k // 2 on each side, as the published model
does; a stride-s conv here is the stride-1 conv read at every s-th pixel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench import refops as R

# stem kind -> (conv ksize, conv stride, max pool (window, stride) or None)
STEMS = {"cifar": (3, 1, None), "imagenet": (7, 2, (3, 2))}
# branch gains tried for an identity block's last conv, largest first
GAINS = tuple(2.0 ** (k / 2) for k in range(4, -9, -1))
TOP_SHARE = 0.02  # the largest share of a block output at the top code


def payload_shape(spec, spatial):
    """A request payload with the given spatial dims: (H, W, C)."""
    return tuple(spatial) + (spec["in_channels"],)


def rung_shape(spec):
    return payload_shape(spec, spec["input_hw"])


def _stage_blocks(spec):
    b = spec["blocks_per_stage"]
    return [b] * len(spec["widths"]) if isinstance(b, int) else list(b)


def _convs(spec):
    """The integer convs in order, as dicts: name, kind ("relu", "proj" or
    "add"), src, ks, stride, cin, cout, shortcut (adds), and the input and
    output plane sides at the rung (``h_in``, ``h``)."""
    ks, stride, pool = STEMS[spec["stem"]]
    h = -(-spec["input_hw"][0] // stride)
    if pool is not None:
        h = -(-h // pool[1])
    out, src, cin = [], "entry", spec["widths"][0]
    sides = {"entry": h}
    kernels = [3, 3] if spec["block"] == "basic" else [1, 3, 1]
    for si, (width, n) in enumerate(zip(spec["widths"],
                                        _stage_blocks(spec))):
        for bi in range(n):
            stride = 2 if si > 0 and bi == 0 else 1
            cout = width * spec["expansion"]
            pre, shortcut = f"s{si}b{bi}", src
            if stride != 1 or cin != cout:
                out.append(dict(name=pre + "_sc", kind="proj", src=src, ks=1,
                                stride=stride, cin=cin, cout=cout))
                shortcut = pre + "_sc"
            c = cin
            for i, k in enumerate(kernels):
                last = i == len(kernels) - 1
                out.append(dict(
                    name=f"{pre}_c{i + 1}", kind="add" if last else "relu",
                    src=src if i == 0 else f"{pre}_c{i}", ks=k,
                    stride=stride if i == kernels.index(3) else 1, cin=c,
                    cout=cout if last else width,
                    shortcut=shortcut if last else None))
                c = width
            src, cin = f"{pre}_c{len(kernels)}", cout
    for c in out:
        c["h_in"] = sides[c["src"]]
        c["h"] = sides[c["name"]] = -(-c["h_in"] // c["stride"])
    return out


def _strided(y, stride):
    return y[:, ::stride, ::stride] if stride > 1 else y


def _int_conv(codes, wc, c):
    p = c["ks"] // 2
    return _strided(R.int_conv(codes, wc, padding=[(p, p)] * 2), c["stride"])


def _stem(x, w, stem, edge):
    """Float stem conv (pad k // 2) and, for the ImageNet stem, the 3x3/2
    max pool (pad 1, padding never the maximum)."""
    _, stride, pool = STEMS[stem]
    h = _strided(R.edge_conv(x, w, edge), stride)
    if pool is not None:
        win, s = pool
        p = win // 2
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, win, win, 1),
                              (1, s, s, 1), ((0, 0), (p, p), (p, p), (0, 0)))
    return h


def _bits(spec):
    q = spec["quant"]
    return (q["bits_a"], q["bits_w"], q["bits_out"])


def _rescale(s_in, s_w, s_out, bits):
    a, w, o = bits
    return R.rescale(s_in, s_w, s_out, bits_a=a, bits_w=w, bits_out=o)


def _add(acc, r, shortcut, n):
    """A block's last conv: signed requant, add the shortcut's codes,
    clip to [0, n]."""
    return jnp.clip(R.requant(acc, r, n_out=n, lo=-n) + shortcut, 0, n)


@functools.partial(jax.jit, static_argnames=("ks", "stride", "cout", "bits"))
def _conv_acc(key, codes, *, ks, stride, cout, bits):
    """He-normal weights of one conv, their ternary scale, and the exact
    integer sums over ``codes``."""
    cin = codes.shape[-1]
    w = R.he_normal(key, (ks, ks, cin, cout), ks * ks * cin)
    s_w = R.ternary_scale(w)
    acc = _int_conv(codes, R.weight_codes(w, s_w, bits=bits[1]),
                    dict(ks=ks, stride=stride))
    return w, s_w, acc


@functools.partial(jax.jit, static_argnames=("bits",))
def _relu_out(acc, s_in, s_w, *, bits):
    s_out = R.out_scale(acc, s_in, s_w, bits_a=bits[0], bits_w=bits[1])
    return s_out, R.requant(acc, _rescale(s_in, s_w, s_out, bits),
                            n_out=R.n_levels(bits[2]))


@functools.partial(jax.jit, static_argnames=("bits",))
def _first_add(acc_p, s_in_p, s_wp, acc, s_in, s_w, *, bits):
    """A stage's stream scale, from the 99th percentile of the real sum of
    its first block's projection and last conv; both requantized onto it,
    and added."""
    n, na_nw = R.n_levels(bits[2]), R.n_levels(bits[0]) * R.n_levels(bits[1])
    real = (acc_p * jnp.exp(s_in_p + s_wp) + acc * jnp.exp(s_in + s_w)) \
        / na_nw
    s_out = jnp.log(jnp.quantile(real, 0.99))
    proj = R.requant(acc_p, _rescale(s_in_p, s_wp, s_out, bits), n_out=n,
                     lo=-n)
    return s_out, proj, _add(acc, _rescale(s_in, s_w, s_out, bits), proj, n)


@functools.partial(jax.jit, static_argnames=("bits",))
def _identity_add(acc, s_in, s_w, s_out, shortcut, *, bits):
    """The largest of ``GAINS`` that leaves at most ``TOP_SHARE`` of the
    block's output at the top code (the smallest if none does), and the
    output under it."""
    n = R.n_levels(bits[2])
    r = _rescale(s_in, s_w, s_out, bits)
    top = jnp.stack([jnp.mean(_add(acc, r * g, shortcut, n) == n)
                     for g in GAINS])
    ok = top <= TOP_SHARE
    g = jnp.asarray(GAINS, jnp.float32)[
        jnp.where(ok.any(), jnp.argmax(ok), len(GAINS) - 1)]
    return g, _add(acc, r * g, shortcut, n)


def checkpoint(spec):
    """Float params and (empty) state of a calibrated FQ ResNet, made on
    the host's CPU and placed on the default device: its ~60 small
    calibration programs (sorts for the quantiles among them) take
    seconds there, where made on a TPU v5e they took 736 s of set-up."""
    model_config(spec)  # a system that cannot build this net fails here
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        params = _calibrated(spec)
    return jax.block_until_ready(jax.device_put(params, jax.devices()[0])), {}


def _calibrated(spec):
    """The checkpoint's params.

    Weights are He-normal with ternary scales as in DarkNet-19's
    checkpoint; the entry quantizer maps the 99th percentile of the
    pooled stem output to the top code. A quantized-ReLU conv's output
    scale maps the 99th percentile of its integer sums on the calibration
    batch to the top code. A stage's stream scale maps the 99th
    percentile of the real sum of its first block's projection and last
    conv to it. An identity block's last conv is scaled by the largest
    gain of ``GAINS`` that leaves at most ``TOP_SHARE`` of the block's
    output at the top code (scaling a conv's weights moves its ternary
    scale by the same factor and leaves its codes as they are), so that
    the stream neither saturates nor loses the branch. Every hand-off
    holds by construction."""
    bits = _bits(spec)
    key = jax.random.key(spec["weight_seed"])
    k_cal, k_stem, k_head = (jax.random.fold_in(key, i) for i in range(3))
    zero = jnp.float32(0.0)
    ks = STEMS[spec["stem"]][0]
    w0 = R.he_normal(k_stem, (ks, ks, spec["in_channels"], spec["widths"][0]),
                     ks * ks * spec["in_channels"])
    params = {"stem": {"w": w0, "s_w": zero, "s_in": zero, "s_out": zero}}
    x = jax.random.normal(k_cal, (2,) + tuple(rung_shape(spec)))
    h = jax.jit(_stem, static_argnums=(2, 3))(x, w0, spec["stem"], "highest")
    s_entry = jnp.log(jnp.quantile(h, 0.99))
    codes = {"entry": R.quantize(h, s_entry, bits=bits[0], lo=0.0)}
    scale = {"entry": s_entry}
    proj = {}  # a projection's weights and sums, until its block's add
    for i, c in enumerate(_convs(spec)):
        name, s_in = c["name"], scale[c["src"]]
        w, s_w, acc = _conv_acc(jax.random.fold_in(key, 3 + i),
                                codes[c["src"]], ks=c["ks"],
                                stride=c["stride"], cout=c["cout"], bits=bits)
        if c["kind"] == "proj":
            proj[name] = (w, s_w, acc, s_in)
            continue
        if c["kind"] == "relu":
            s_out, codes[name] = _relu_out(acc, s_in, s_w, bits=bits)
        elif c["shortcut"] in proj:
            sc = c["shortcut"]
            wp, s_wp, acc_p, s_in_p = proj.pop(sc)
            s_out, codes[sc], codes[name] = _first_add(
                acc_p, s_in_p, s_wp, acc, s_in, s_w, bits=bits)
            scale[sc] = s_out
            params[sc] = {"w": wp, "s_w": s_wp, "s_in": s_in_p,
                          "s_out": s_out}
        else:
            s_out = scale[c["shortcut"]]
            g, codes[name] = _identity_add(acc, s_in, s_w, s_out,
                                           codes[c["shortcut"]], bits=bits)
            w, s_w = w * g, s_w + jnp.log(g)
        scale[name] = s_out
        params[name] = {"w": w, "s_w": s_w, "s_in": s_in, "s_out": s_out}
    cin = c["cout"]
    params["head"] = {"w": R.he_normal(k_head, (cin, spec["num_classes"]),
                                       cin),
                      "b": jnp.zeros((spec["num_classes"],), jnp.float32)}
    return params


def model_config(spec):
    from repro.models import resnet
    return resnet.ResNetConfig(
        widths=tuple(spec["widths"]),
        blocks_per_stage=tuple(_stage_blocks(spec)),
        num_classes=spec["num_classes"], quantize_first_last=False,
        block=spec["block"], expansion=spec["expansion"], stem=spec["stem"],
        in_channels=spec["in_channels"])


def quant_config(spec):
    from repro.core.quant import QuantConfig
    q = spec["quant"]
    return QuantConfig(q["bits_w"], q["bits_a"], q["bits_out"], fq=q["fq"])


def program(spec, params, state, devices):
    """The served system: (ladder, one apply fn per lane), each lane over
    its own placed copy of the converted stack when there are several."""
    from repro.core.integer_inference import replicate_stack
    from repro.models import frontends, resnet
    cfg, qcfg = model_config(spec), quant_config(spec)
    ip = resnet.convert_int(params, state, qcfg, cfg,
                            weight_format=spec["weight_format"])
    stacks = [ip] if len(devices) == 1 else replicate_stack(ip, devices)
    ladder = frontends.resnet_serving_ladder(cfg, spec["batcher"]["rungs"])
    return ladder, [resnet.int_serve_fn(s, qcfg, cfg) for s in stacks]


class Reference:
    """The plain model over a checkpoint, its tables recomputed from the
    float params by the paper's formulas; ``edge`` is "highest" for the
    reference and "bf16x3" for the control."""

    BLOCK = 8

    def __init__(self, spec, params, state, edge="highest"):
        bits = _bits(spec)
        self.spec, self.edge = spec, edge
        self.convs = _convs(spec)
        first, last = self.convs[0]["name"], self.convs[-1]["name"]
        self.tables = {
            "stem": params["stem"]["w"],
            "s_entry": params[first]["s_in"],
            "codes": {c["name"]: R.weight_codes(
                params[c["name"]]["w"], params[c["name"]]["s_w"],
                bits=bits[1]).astype(jnp.bfloat16) for c in self.convs},
            "scales": {c["name"]: _rescale(
                params[c["name"]]["s_in"], params[c["name"]]["s_w"],
                params[c["name"]]["s_out"], bits) for c in self.convs},
            "dec": jnp.exp(params[last]["s_out"]) / R.n_levels(bits[2]),
            "head": params["head"]["w"], "bias": params["head"]["b"],
        }
        self._fwd = jax.jit(lambda x: self._forward(self.tables, x))

    def _forward(self, t, x):
        bits = _bits(self.spec)
        n = R.n_levels(bits[2])
        h = _stem(x, t["stem"], self.spec["stem"], self.edge)
        vals = {"entry": R.quantize(h, t["s_entry"], bits=bits[0], lo=0.0)}
        for c in self.convs:
            acc = _int_conv(vals[c["src"]], t["codes"][c["name"]], c)
            r = t["scales"][c["name"]]
            if c["kind"] == "relu":
                vals[c["name"]] = R.requant(acc, r, n_out=n)
            elif c["kind"] == "proj":
                vals[c["name"]] = R.requant(acc, r, n_out=n, lo=-n)
            else:
                vals[c["name"]] = _add(acc, r, vals[c["shortcut"]], n)
        y = jnp.mean(vals[self.convs[-1]["name"]] * t["dec"], axis=(1, 2))
        return R.edge_matmul(y, t["head"], self.edge) + t["bias"]

    def logits(self, payloads):
        """Reference logits of raw payloads (letterboxed here to the rung)."""
        return R.run_blocks(self._fwd, payloads, self.spec["input_hw"],
                            self.BLOCK)


def request_ops(spec):
    """Operations one request needs at the rung: the integer convs (2 per
    MAC) and the float edges (stem conv, classifier)."""
    ks, stride, _ = STEMS[spec["stem"]]
    h = -(-spec["input_hw"][0] // stride)
    w = -(-spec["input_hw"][1] // stride)
    convs = _convs(spec)
    stem = 2 * h * w * ks * ks * spec["in_channels"] * spec["widths"][0]
    return {"int": sum(_macs(c, 1) for c in convs) * 2,
            "float": stem + 2 * convs[-1]["cout"] * spec["num_classes"]}


def _macs(c, batch):
    return batch * c["h"] * c["h"] * c["ks"] * c["ks"] * c["cin"] * c["cout"]


def _call(c, spec, batch):
    """(ops, bytes) of one conv call: the input codes read, the weights in
    their stored format and the output codes written."""
    return (2 * _macs(c, batch),
            batch * c["h_in"] * c["h_in"] * c["cin"]
            + c["ks"] * c["ks"] * c["cin"] * c["cout"]
            * R.CODE_BYTES[spec["weight_format"]]
            + batch * c["h"] * c["h"] * c["cout"])


def fq_conv_calls(spec, batch):
    """(ops, bytes) of each plain ``fq_conv2d`` call (projections and the
    branch convs before each block's last) in one step of ``batch``
    requests at the rung. Ops count valid output pixels only."""
    return [_call(c, spec, batch) for c in _convs(spec) if c["kind"] != "add"]


def fq_conv_add_calls(spec, batch):
    """(ops, bytes) of each ``fq_conv2d_add`` call (each block's last conv
    with its residual add): a plain call's, plus the shortcut's codes
    read."""
    return [(ops, nbytes + batch * c["h"] * c["h"] * c["cout"])
            for c in _convs(spec) if c["kind"] == "add"
            for ops, nbytes in [_call(c, spec, batch)]]
