"""Mutation sweep: inject one contract violation per test and require the
analyzer to (a) emit the specific finding and (b) gate with a non-zero
exit code. This is the proof that every pass actually fires — a verifier
that can't fail is not verifying anything.

Covered violation classes:
  1. scale hand-off mismatch            (planlint/handoff)
  2. float leak in the integer core     (intlint/float-leak)
  3. int32 accumulator overflow depth   (intlint/acc-overflow)
  4. narrow (int16) accumulator         (intlint/narrow-accumulator)
  5. float output without dequant decl  (intlint/float-output)
  6. noise-seed collision               (planlint/seed-collision)
  7. malformed autotune table rows      (kernellint/table-schema)
  8. over-budget VMEM block pick        (kernellint/vmem)
  9. unmeasured served shape            (kernellint/autotune-miss)
 10. non-divisor table bc drift         (kernellint/table-drift)
 11. degenerate / stale rescale         (planlint/rescale)
 12. static-aux disagreement            (planlint/static-aux)
 13. weight codes out of range          (planlint/code-range)
 14. fused-pool bookkeeping break       (planlint/fused-pool)
 15. final=True mid-chain               (planlint/spec-mismatch)
 16. lane-illegal block on tpu           (kernellint/blockspec)
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import intlint, kernellint, planlint, targets
from repro.analysis.__main__ import main as cli_main
from repro.analysis.intlint import TraceSpec
from repro.analysis.kernellint import ConvShape
from repro.analysis.report import Report, Severity
from repro.core import integer_inference as ii

pytestmark = pytest.mark.mutation


@pytest.fixture(scope="module")
def kws_t():
    return targets.kws_target(reduced=True)


@pytest.fixture(scope="module")
def dark_t():
    return targets.darknet_target(reduced=True)


def checks(report):
    return {f.check for f in report.findings}


def assert_caught(report, check):
    assert check in checks(report), \
        f"expected {check}, got {sorted(checks(report))}"
    assert report.exit_code() == 1


def mutated_stack(stack, name, **kv):
    layers = {n: dict(d) for n, d in stack.layers.items()}
    layers[name].update(kv)
    return ii.ConvertedStack(stack.qcfg, stack.specs, layers,
                             dict(stack.extras))


# -- planlint ----------------------------------------------------------------


def test_handoff_mismatch_caught(kws_t):
    params = {n: dict(p) for n, p in kws_t.fq_params.items()}
    params["conv1"]["s_in"] = jnp.float32(0.9)   # chain ties it to 0.2
    r = Report()
    planlint.lint_handoff(params, kws_t.chain, r, "mut")
    assert_caught(r, "planlint/handoff")


def test_stale_decode_scale_caught(kws_t):
    stack = mutated_stack(kws_t.stack, kws_t.chain[0])
    stack.extras["s_out_last"] = jnp.float32(7.7)
    r = Report()
    planlint.lint_stack(stack, r, "mut", layer_params=kws_t.fq_params)
    assert_caught(r, "planlint/handoff")


def test_seed_collision_caught():
    r = Report()
    planlint.lint_seed_values([7, 8, 7], ["c0", "c1", "c2"], r, "mut")
    assert_caught(r, "planlint/seed-collision")
    assert "c0" in r.findings[0].details["layers"]


def test_zero_rescale_caught(kws_t):
    r = Report()
    planlint.lint_stack(mutated_stack(kws_t.stack, kws_t.chain[1],
                                      rescale=jnp.float32(0.0)), r, "mut")
    assert_caught(r, "planlint/rescale")


def test_subnormal_rescale_caught(kws_t):
    r = Report()
    planlint.lint_stack(mutated_stack(kws_t.stack, kws_t.chain[1],
                                      rescale=1e-42), r, "mut")
    assert_caught(r, "planlint/rescale")


def test_stale_rescale_vs_params_caught(kws_t):
    """A rescale that no longer refolds from the source scales = the
    stack artifact is stale relative to its training params."""
    old = float(np.asarray(kws_t.stack.layers[kws_t.chain[1]]["rescale"]))
    r = Report()
    planlint.lint_stack(
        mutated_stack(kws_t.stack, kws_t.chain[1],
                      rescale=jnp.float32(old * 2)),
        r, "mut", layer_params=kws_t.fq_params)
    assert_caught(r, "planlint/rescale")


def test_static_aux_mismatch_caught(kws_t):
    r = Report()
    planlint.lint_stack(mutated_stack(kws_t.stack, kws_t.chain[0],
                                      n_out=31), r, "mut")
    assert_caught(r, "planlint/static-aux")


def test_traced_static_aux_caught(kws_t):
    """A quantizer static that became a traced array would silently
    specialize the kernel — must be a python int."""
    r = Report()
    planlint.lint_stack(mutated_stack(kws_t.stack, kws_t.chain[0],
                                      n_w=jnp.int32(7)), r, "mut")
    assert_caught(r, "planlint/static-aux")


def test_code_range_violation_caught(kws_t):
    layer = kws_t.stack.layers[kws_t.chain[0]]
    bad = np.asarray(layer["w_codes"]).copy()
    bad.flat[0] = 100                            # n_w for W2 is 1
    r = Report()
    planlint.lint_stack(mutated_stack(kws_t.stack, kws_t.chain[0],
                                      w_codes=jnp.asarray(bad)), r, "mut")
    assert_caught(r, "planlint/code-range")


def test_dropped_pool_caught(dark_t):
    r = Report()
    planlint.lint_fused_pools(dark_t.plan, dark_t.n_pool_markers + 1, r,
                              "mut", stack=dark_t.stack)
    assert_caught(r, "planlint/fused-pool")


def test_final_mid_chain_caught(kws_t):
    specs = list(kws_t.stack.specs)
    specs[0] = ii.LayerSpec(specs[0].name, final=True)
    bad = ii.ConvertedStack(kws_t.stack.qcfg, specs, kws_t.stack.layers,
                            kws_t.stack.extras)
    r = Report()
    planlint.lint_stack(bad, r, "mut")
    assert_caught(r, "planlint/spec-mismatch")


# -- intlint -----------------------------------------------------------------


def test_float_leak_caught():
    w = jnp.ones((8, 4), jnp.float32)

    def leaky(codes):
        return codes.astype(jnp.float32) @ w     # float dot on codes

    r = Report()
    intlint.lint_trace(TraceSpec("mut/float-leak", leaky,
                                 (jnp.zeros((2, 8), jnp.int8),),
                                 expect_float_out=True), r)
    assert_caught(r, "intlint/float-leak")
    assert not r.proofs                          # nothing proved


def test_acc_overflow_depth_caught():
    k = 300_000
    w = jnp.full((k, 4), 127, jnp.int8)          # |codes| 128 x 127 x 300k

    def deep(codes):
        return jax.lax.dot_general(
            codes.astype(jnp.int32), w.astype(jnp.int32),
            (((1,), (0,)), ((), ())))

    r = Report()
    intlint.lint_trace(TraceSpec("mut/overflow", deep,
                                 (jnp.zeros((1, k), jnp.int8),)), r)
    assert_caught(r, "intlint/acc-overflow")


def test_narrow_accumulator_caught():
    w = jnp.ones((8, 4), jnp.int8)

    def narrow(codes):
        return jax.lax.dot_general(
            codes, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int16)

    r = Report()
    intlint.lint_trace(TraceSpec("mut/narrow", narrow,
                                 (jnp.zeros((2, 8), jnp.int8),)), r)
    assert_caught(r, "intlint/narrow-accumulator")


def test_float_output_caught():
    def dequant(codes):
        return codes.astype(jnp.float32) * 0.05

    r = Report()
    intlint.lint_trace(TraceSpec("mut/float-out", dequant,
                                 (jnp.zeros((4,), jnp.int8),)), r)
    assert_caught(r, "intlint/float-output")


# -- kernellint --------------------------------------------------------------


def _write_table(tmp_path, entries, **doc):
    p = tmp_path / "table.json"
    body = {"format": 1, "backend": jax.default_backend(),
            "entries": entries}
    body.update(doc)
    p.write_text(json.dumps(body))
    return str(p)


def test_malformed_table_rows_caught(tmp_path):
    path = _write_table(tmp_path, [
        {"kh": 3, "kw": 3, "stride": 1, "bc": 0},          # non-positive
        {"kh": 3, "kw": 3, "stride": 1, "bco": 64},        # duplicate key
        {"kh": "x", "kw": 3, "stride": 1},                 # bad key field
        17,                                                # not an object
    ])
    r = Report()
    kernellint.lint_table_schema(r, path)
    assert_caught(r, "kernellint/table-schema")
    assert sum(1 for f in r.findings
               if f.check == "kernellint/table-schema") >= 4


def test_wrong_format_tag_caught(tmp_path):
    path = _write_table(tmp_path, [], format=2)
    r = Report()
    kernellint.lint_table_schema(r, path)
    assert_caught(r, "kernellint/table-schema")


def test_vmem_blowout_caught():
    shape = ConvShape("mut/conv", ho=224, wo=224, cin=32, cout=64,
                      kh=3, kw=3)
    r = Report()
    kernellint.lint_shapes(
        [shape], r, backend="cpu",
        table={(3, 3, 1, "int8"): {"bho": 224, "bco": 64}},
        measured={(3, 3, 1, "int8")})
    assert_caught(r, "kernellint/vmem")


@pytest.mark.parametrize("knobs", [{"bco": 64}, {"bc": 64}])
def test_lane_illegal_block_caught_on_tpu(knobs):
    """A channel block that is neither the whole extent nor a 128-lane
    multiple is refused by Mosaic; on the tpu backend it is a lint error,
    while an interpret-mode backend takes it."""
    shape = ConvShape("mut/conv", ho=28, wo=28, cin=256, cout=256,
                      kh=3, kw=3)
    key = (3, 3, 1, "int8")
    r = Report()
    kernellint.lint_shapes([shape], r, backend="tpu", table={key: knobs},
                           measured={key})
    assert_caught(r, "kernellint/blockspec")
    r = Report()
    kernellint.lint_shapes([shape], r, backend="cpu", table={key: knobs},
                           measured={key})
    assert not [f for f in r.findings if f.check == "kernellint/blockspec"
                and f.severity >= Severity.ERROR]


def test_unmeasured_shape_warned():
    shape = ConvShape("mut/conv", ho=28, wo=28, cin=32, cout=64,
                      kh=7, kw=7)
    r = Report()
    kernellint.lint_shapes([shape], r, backend="cpu", table={},
                           measured=set())
    assert_caught(r, "kernellint/autotune-miss")
    assert r.counters["kernellint/autotune-misses"] == 1


def test_table_bc_drift_warned():
    """A measured bc that doesn't divide a served cin silently rounds
    down at serve time — the lint must surface the drift."""
    shape = ConvShape("mut/conv", ho=28, wo=28, cin=100, cout=45,
                      kh=3, kw=1)
    r = Report()
    kernellint.lint_shapes([shape], r, backend="cpu",
                           table={(3, 1, 1, "int8"): {"bc": 45}},
                           measured={(3, 1, 1, "int8")})
    assert_caught(r, "kernellint/table-drift")
    assert r.findings[0].details["effective_bc"] == 25


# -- end-to-end gate ---------------------------------------------------------


def test_cli_gates_on_broken_table(tmp_path):
    """The CLI exit code (what `make analyze` sees) goes non-zero for a
    candidate table with a malformed row."""
    path = _write_table(tmp_path, [
        {"kh": 3, "kw": 3, "stride": 1, "bc": -4},
    ])
    rc = cli_main(["--stack", "kws", "--reduced", "--skip-intlint",
                   "--table", path,
                   "--json", str(tmp_path / "rep.json")])
    assert rc == 1
    rep = json.loads((tmp_path / "rep.json").read_text())
    assert any(f["check"] == "kernellint/table-schema"
               for f in rep["findings"])


# -- packed-weight mutations -------------------------------------------------


@pytest.fixture(scope="module")
def kws_packed_t():
    return targets.kws_target(reduced=True, weight_format="auto")


def test_packed_sign_extension_bug_caught():
    """Unpack without the two's-complement sign extension leaves ternary
    fields in [0, 3] instead of [-2, 1]; the weight-range interval check
    on the contraction's rhs operand must fire."""
    from repro.core import quant
    fmt, K, N = "ternary", 12, 4
    codes = np.random.default_rng(0).integers(-1, 2, (K, N)).astype(np.int8)
    packed = quant.pack_codes(jnp.asarray(codes), fmt)
    bits, factor = 2, 4
    mask = (1 << bits) - 1

    def buggy_core(a, p):
        p32 = p.astype(jnp.int32)
        fields = [(p32 >> (i * bits)) & mask for i in range(factor)]
        w = jnp.stack(fields, axis=1).reshape(-1, p.shape[1])[:K]
        acc = jnp.dot(a.astype(jnp.int32), w)
        return jnp.clip(jnp.round(acc * 0.01), -7, 7).astype(jnp.int8)

    r = Report()
    intlint.lint_trace(TraceSpec(
        "mut/sign-extension", buggy_core,
        (jnp.zeros((2, K), jnp.int8), packed),
        weight_range=quant.format_interval(fmt)), r)
    assert_caught(r, "intlint/weight-range")

    # ...and the CORRECT unpack on the same packed bytes stays clean
    def good_core(a, p):
        w = quant.unpack_codes(p, fmt, rows=K).astype(jnp.int32)
        acc = jnp.dot(a.astype(jnp.int32), w)
        return jnp.clip(jnp.round(acc * 0.01), -7, 7).astype(jnp.int8)

    r2 = Report()
    intlint.lint_trace(TraceSpec(
        "mut/sign-extension-ok", good_core,
        (jnp.zeros((2, K), jnp.int8), packed),
        weight_range=quant.format_interval(fmt)), r2)
    assert "intlint/weight-range" not in checks(r2)
    assert r2.exit_code() == 0


def test_packed_out_of_range_code_caught(kws_packed_t):
    """A tampered ternary byte whose 2-bit field decodes to -2 (< -n_w=-1)
    must trip the code-range check on the DECODED codes."""
    name = kws_packed_t.chain[0]
    layer = kws_packed_t.stack.layers[name]
    assert layer["weight_format"] == "ternary"
    bad = np.asarray(layer["w_codes"]).copy()
    bad.flat[0] = 0b10                           # field 0 -> -2
    r = Report()
    planlint.lint_stack(mutated_stack(kws_packed_t.stack, name,
                                      w_codes=jnp.asarray(bad)), r, "mut")
    assert_caught(r, "planlint/code-range")


def test_unknown_packed_table_format_caught(tmp_path):
    path = _write_table(tmp_path, [
        {"kh": 3, "kw": 3, "stride": 1, "bco": 64, "format": "int3"},
    ])
    r = Report()
    kernellint.lint_table_schema(r, path)
    assert_caught(r, "kernellint/table-schema")
    assert any("int3" in f.message for f in r.findings)


def test_packed_format_spec_mismatch_caught(kws_packed_t):
    """A layer re-packed into a different format than its spec declares
    would silently rederive into a different layout."""
    name = kws_packed_t.chain[0]
    r = Report()
    planlint.lint_stack(mutated_stack(kws_packed_t.stack, name,
                                      weight_format="int8"), r, "mut")
    assert_caught(r, "planlint/weight-format")
