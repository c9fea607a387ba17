"""The static limb-bound tracker (ISSUE 12): the int32-safety audit of
the field pipeline is CHECKED code — these tests pin that it passes over
every live formula and that it fails loudly on a deliberately-overflowing
chain."""

import pytest

pytest.importorskip("jax")

from tpunode.verify import bounds as B
from tpunode.verify import field as F


def test_audit_passes_live_formulas():
    """The acceptance gate: every live formula body from the window
    loop's input bounds — no overflow, and output coordinates stay
    inside the 2^13 closure the MSM feeds back."""
    out = B.audit_formulas()
    assert set(out) == {"pt_add", "pt_double"}
    for name, peak in out.items():
        assert 0 < peak <= B.COORD_BOUND, (name, peak)


def test_overflow_chain_fails_loudly():
    """A synthetic chain that violates int32 headroom must raise at
    'trace time' (the audit), not corrupt silently: two maximally loose
    2^20-limb operands convolve past 2^31."""
    bf = B.BoundField()
    fat = B.BVal.uniform(1 << 20)
    with pytest.raises(B.BoundOverflow):
        bf.mul_t(fat, fat)
    # accumulating too many legal wides also trips the tracker
    w = bf.mul_t_wide(B.BVal.uniform(1 << 13), B.BVal.uniform(1 << 13))
    with pytest.raises(B.BoundOverflow):
        bf.acc_add(*([w] * 16))


def test_documented_output_contracts_enforced():
    """_reduce_wide's docstring bounds (|limb| <= 2^12, loose <= 2^13)
    are asserted by the tracker, not just written down."""
    bf = B.BoundField()
    a = B.BVal.uniform(1 << 13)
    tight = bf.mul_t(a, a)
    assert tight.max() <= 1 << 12
    loose = bf.reduce_wide_loose(bf.mul_t_wide(a, a))
    assert loose.max() <= 1 << 13
    # the loose output is a legal mul_t operand and coordinate
    bf.mul_t(loose, loose)


def test_carry_bound_is_sound_numerically():
    """The tracker's carry-round interval arithmetic really bounds the
    implementation: run field._carry on adversarial int32 vectors and
    compare against the tracked bound."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    bound = 1 << 17
    tracked = B._carry(B.BVal.uniform(bound), 1)
    for _ in range(20):
        x = rng.integers(-bound, bound + 1, size=(F.NLIMBS, 4))
        got = np.asarray(F._carry(jnp.asarray(x.astype(np.int32)), 1))
        assert (np.abs(got) <= np.array(tracked.b)[:, None]).all()


def test_assert_formulas_safe_is_cached(monkeypatch):
    B._AUDITED.clear()
    B.assert_formulas_safe()
    assert set(B._AUDITED) == {"pt_add", "pt_double"}

    def boom():
        raise AssertionError("audited twice")

    monkeypatch.setattr(B, "audit_formulas", boom)
    B.assert_formulas_safe()  # second call: cached, no replay


def test_bval_ops():
    a = B.BVal((1, 2, 3))
    b = B.BVal((10, 20, 30))
    assert (a + b).b == (11, 22, 33)
    assert (a - b).b == (11, 22, 33)  # magnitudes add under subtraction
    assert (-a).b == a.b
    assert (a * -4).b == (4, 8, 12)  # |k| scaling
    with pytest.raises(B.BoundOverflow):
        B.BVal.uniform((1 << 30)) + B.BVal.uniform(1 << 30)
