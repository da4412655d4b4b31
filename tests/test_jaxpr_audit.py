"""Jaxpr auditor tests: every rule must fire on a deliberately-poisoned
function (f64 widening, host callbacks — including inside scan bodies —
giant baked-in constants, dead donation, implicit promotion) and stay
silent on clean graphs; waivers must be reasoned; and the real registered
targets must audit clean (slow tier — CI runs the CLI equivalent)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.analysis import jaxpr_audit
from alphafold2_tpu.analysis.targets import TraceTarget, default_targets


def synthetic(name, fn, args, donate=(), allow=frozenset(), reasons=None):
    return TraceTarget(
        name=name, build=lambda: (fn, args), donate_argnums=donate,
        allow=allow, allow_reasons=reasons,
    )


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- jaxpr rules


def test_clean_function_has_no_findings():
    t = synthetic("clean", lambda x: x * 2.0 + 1.0, (jnp.ones((4,)),))
    assert jaxpr_audit.audit_target(t) == []


def test_f64_widening_rejected():
    with jax.enable_x64(True):

        def poisoned(x):
            return x.astype(jnp.float64) * 2.0

        t = synthetic(
            "f64", poisoned, (jnp.ones((4,), jnp.float32),)
        )
        findings = jaxpr_audit.audit_target(t)
    assert "AF2A101" in rules_of(findings), findings
    assert any("float64" in f.message for f in findings)


def test_host_callback_rejected():
    def poisoned(x):
        return jax.pure_callback(
            lambda v: np.sin(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    t = synthetic("cb", poisoned, (jnp.ones((4,)),))
    findings = jaxpr_audit.audit_target(t)
    assert rules_of(findings) == ["AF2A102"], findings


def test_host_callback_found_inside_scan_body():
    """The traversal must recurse into control-flow sub-jaxprs."""

    def poisoned(xs):
        def body(carry, x):
            y = jax.pure_callback(
                lambda v: np.abs(v), jax.ShapeDtypeStruct((), xs.dtype), x
            )
            return carry + y, y

        total, _ = jax.lax.scan(body, jnp.zeros(()), xs)
        return total

    t = synthetic("cb_scan", poisoned, (jnp.ones((8,)),))
    assert "AF2A102" in rules_of(jaxpr_audit.audit_target(t))


def test_giant_baked_constant_rejected():
    big = jnp.zeros((600, 600), jnp.float32)  # 1.44 MB closed over

    def poisoned(x):
        return x + big[0, 0]

    t = synthetic("const", poisoned, (jnp.ones(()),))
    findings = jaxpr_audit.audit_target(t)
    assert rules_of(findings) == ["AF2A103"], findings
    # raising the threshold clears it
    assert jaxpr_audit.audit_target(t, const_threshold=2 << 20) == []


def test_dead_donation_flagged_and_waivable():
    def fwd(tokens):
        return tokens.astype(jnp.float32) * 2.0

    args = (jnp.zeros((8,), jnp.int32),)
    t = synthetic("donate", fwd, args, donate=(0,))
    findings = jaxpr_audit.audit_target(t)
    assert rules_of(findings) == ["AF2A104"], findings

    waived = synthetic(
        "donate", fwd, args, donate=(0,),
        allow=frozenset({"AF2A104"}),
        reasons={"AF2A104": "int buffers intentionally freed early"},
    )
    assert jaxpr_audit.audit_target(waived) == []


def test_matching_donation_is_clean():
    t = synthetic(
        "donate_ok", lambda x: x * 2.0, (jnp.ones((8,)),), donate=(0,)
    )
    assert jaxpr_audit.audit_target(t) == []


def test_strict_promotion_violation_flagged():
    def poisoned(m, x):
        return m * x  # bool * f32: implicit promotion

    t = synthetic(
        "promo", poisoned, (jnp.ones((4,), bool), jnp.ones((4,)))
    )
    findings = jaxpr_audit.audit_target(t)
    assert rules_of(findings) == ["AF2A105"], findings


def test_build_failure_is_a_finding():
    def exploding_build():
        raise RuntimeError("no such checkpoint")

    t = TraceTarget(name="broken", build=exploding_build)
    findings = jaxpr_audit.audit_target(t)
    assert rules_of(findings) == ["AF2A100"]
    assert "no such checkpoint" in findings[0].message


def test_waiver_without_reason_is_rejected():
    with pytest.raises(ValueError, match="without a reason"):
        TraceTarget(
            name="bad", build=lambda: (lambda x: x, (jnp.ones(()),)),
            allow=frozenset({"AF2A104"}),
        )


# ------------------------------------------------ deep sub-jaxpr recursion


def test_callback_buried_in_custom_vjp_bwd():
    """The violation hides in the custom_vjp *backward* body — reachable
    only through the fwd/bwd thunks iter_eqns_deep unpacks, never through
    the plain forward trace."""

    @jax.custom_vjp
    def f(x):
        return x * 2.0

    def f_fwd(x):
        return f(x), x

    def f_bwd(res, g):
        jax.debug.callback(lambda v: None, res)
        return (g * 2.0,)

    f.defvjp(f_fwd, f_bwd)

    t = synthetic("vjp", lambda x: f(x).sum(), (jnp.ones((4,)),))
    assert "AF2A102" in rules_of(jaxpr_audit.audit_target(t))


def test_callback_buried_in_custom_jvp_rule():
    @jax.custom_jvp
    def g(x):
        return x * 2.0

    @g.defjvp
    def g_jvp(primals, tangents):
        (x,), (t,) = primals, tangents
        jax.debug.callback(lambda v: None, x)
        return g(x), t * 2.0

    t = synthetic("jvp", lambda x: g(x).sum(), (jnp.ones((4,)),))
    assert "AF2A102" in rules_of(jaxpr_audit.audit_target(t))


def test_callback_buried_in_nested_jit():
    inner = jax.jit(
        lambda x: jax.pure_callback(
            lambda v: np.sin(v), jax.ShapeDtypeStruct((4,), jnp.float32), x
        )
    )
    t = synthetic("pjit", lambda x: inner(x) + 1.0, (jnp.ones((4,)),))
    assert "AF2A102" in rules_of(jaxpr_audit.audit_target(t))


def test_clean_custom_vjp_recursion_terminates():
    """The standard fwd-calls-f pattern re-embeds the custom_vjp_call in
    its own forward body; the signature seen-guard must terminate the walk
    and report nothing."""

    @jax.custom_vjp
    def f(x):
        return x * 2.0

    def f_fwd(x):
        return f(x), x

    def f_bwd(res, g):
        return (g * 2.0,)

    f.defvjp(f_fwd, f_bwd)
    t = synthetic("vjp_ok", lambda x: f(x).sum(), (jnp.ones((4,)),))
    assert jaxpr_audit.audit_target(t) == []


# ---------------------------------------------------------- real targets


@pytest.mark.slow
def test_registered_targets_audit_clean():
    """The shipped model/train/serve executables carry no findings — the
    CI jaxpr-audit job's in-suite twin."""
    findings = jaxpr_audit.audit(default_targets())
    assert findings == [], "\n".join(f.format() for f in findings)


# ------------------------------------------------------- lowering fold-in


def test_lowering_gate_refusal_surfaces_as_finding():
    """A gate run that certifies nothing (typo'd case name) must produce a
    finding, never silent green."""
    findings = jaxpr_audit.lowering_findings(["no_such_case"])
    assert rules_of(findings) == ["AF2A106"]
    assert "unknown case" in findings[0].message


@pytest.mark.slow
def test_lowering_negative_control_folds_in_clean():
    """The gate's own negative control passes through the auditor's
    findings stream with zero findings (the mis-tiled kernel is rejected,
    which is the case SUCCEEDING)."""
    findings = jaxpr_audit.lowering_findings(
        ["negative_control_rejects_bad_tiling"]
    )
    assert findings == [], [f.format() for f in findings]
