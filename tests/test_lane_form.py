"""The form in which a lane crosses from host prep to the jitted programs
(PR 39): one ``(ROWS, B)`` int32 buffer, written by the native prep or by
numpy, expanded to verify_core's sixteen arrays on the device.  The
reference expansion is tests/lane_ref.py (Python ints + the numpy
conversions the host used to run)."""

import inspect
import itertools
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import lane_ref as R
from tpunode.metrics import metrics
from tpunode.verify import field as F
from tpunode.verify import kernel as K
from tpunode.verify.cpu_native import LANE_ROWS, load_native_verifier
from tpunode.verify.ecdsa_cpu import (
    CURVE_N,
    CURVE_P,
    GENERATOR,
    bip340_challenge,
    lift_x,
    point_mul,
    schnorr_challenge,
    sign,
    sign_bip340,
    sign_schnorr,
)
from tpunode.verify.raw import pack_items

rng = random.Random(0x39)
needs_native = pytest.mark.skipif(
    load_native_verifier() is None, reason="native library unavailable"
)


def _signed(kind: str, i: int = 0) -> tuple:
    """One valid item of ``kind`` (ecdsa | schnorr | bip340)."""
    priv = (0xA11CE + 7919 * i) % CURVE_N
    pub = point_mul(priv, GENERATOR)
    z = (0xD00D << (i % 200)) % CURVE_N
    if kind == "schnorr":
        r, s = sign_schnorr(priv, z, 0xC0FFEE + i)
        return (pub, schnorr_challenge(r, pub, z), r, s, "schnorr")
    if kind == "bip340":
        r, s = sign_bip340(priv, z, 0xC0FFEE + i)
        return (lift_x(pub.x), bip340_challenge(r, pub.x, z), r, s, "bip340")
    r, s = sign(priv, z, 0xC0FFEE + i)
    return (pub, z, r, s)


def _batch(kinds: str, n: int) -> list:
    """``kinds``: 'mixed', 'ecdsa' or 'schnorr' (both Schnorr variants)."""
    cycle = {"mixed": ("ecdsa", "schnorr", "bip340"), "ecdsa": ("ecdsa",),
             "schnorr": ("schnorr", "bip340")}[kinds]
    return [_signed(cycle[i % len(cycle)], i) for i in range(n)]


Q = _signed("ecdsa")[0]
EDGE_BATCHES = {
    "empty": [],
    "one": [_signed("ecdsa", 1)],
    "one_schnorr": [_signed("schnorr", 2)],
    "absent_rows": [(None, 1, 1, 1), _signed("ecdsa", 3), (None, 5, 6, 7),
                    _signed("bip340", 4)],
    "r_ranges": [(Q, 9, 0, 5), (Q, 9, CURVE_N, 5), (Q, 9, CURVE_N - 1, 5),
                 (Q, 9, 5, 0), (Q, 9, 5, CURVE_N + 7), (Q, 9, 5, CURVE_N - 1)],
    "huge_digest": [(Q, 1 << 300, 5, 7), (Q, CURVE_N, 5, 7), (Q, 0, 5, 7)],
    # r + n just under p (a second candidate) and at p (none)
    "r_plus_n": [(Q, 9, CURVE_P - CURVE_N - 1, 5), (Q, 9, CURVE_P - CURVE_N, 5),
                 (Q, 9, CURVE_P - CURVE_N + 1, 5)],
    "schnorr_ranges": [(Q, 9, CURVE_P - 1, 0, "schnorr"),
                       (Q, 9, CURVE_P, 1, "schnorr"),
                       (Q, 0, 0, CURVE_N - 1, "bip340"),
                       (Q, 9, 1, CURVE_N, "bip340")],
    "mixed": _batch("mixed", 23),
}


# --- host side: the two writers agree, byte for byte --------------------------


@needs_native
@pytest.mark.parametrize("pad", [None, 32, 256])
@pytest.mark.parametrize("name", list(EDGE_BATCHES))
def test_native_buffer_equals_numpy_reference_buffer(name, pad):
    items = EDGE_BATCHES[name]
    if pad is None and not items:
        pad = 1  # a zero-width lane has no form
    py = K.prepare_batch(items, pad_to=pad, native=False)
    nat = K.prepare_batch(items, pad_to=pad, native=True)
    for prep in (py, nat):
        assert prep.count == len(items)
        assert prep.buf.shape == (K.ROWS, pad or len(items))
        assert prep.buf.dtype == np.int32 and prep.buf.flags.c_contiguous
    assert py.buf.tobytes() == nat.buf.tobytes()
    # the raw (native-extractor) entry writes the same buffer
    raw = K.prepare_batch_raw(pack_items(items), pad_to=pad)
    assert raw.buf.tobytes() == py.buf.tobytes()
    # padding and refused rows are zero columns
    dead = ~R.flag(py.buf, "host_valid")
    assert not py.buf[:, dead].any()
    assert dead[len(items):].all()


def test_layout_constants_agree_across_the_three_writers():
    assert K.ROWS == LANE_ROWS == 53
    assert K.FLAGS_ROW == 4 * K.HALF_WORDS + 4 * K.FIELD_WORDS == 52
    assert 32 * K.HALF_WORDS >= K.WINDOW_BITS * K.WINDOWS
    assert 32 * K.FIELD_WORDS == 256 <= F.RADIX * F.NLIMBS
    assert K.FLAG_NAMES == tuple(
        n for n, nd in R.DEVICE_FIELDS if nd == 1)  # bit order = arg order


@pytest.mark.parametrize("native", [False, pytest.param(True, marks=needs_native)])
def test_prepared_values_are_the_items(native):
    """What the buffer says, read back with Python ints: u1·G + u2·Q's
    scalars recombine, the key and r are the item's, r + n only when it is
    below p."""
    items = EDGE_BATCHES["mixed"] + EDGE_BATCHES["r_plus_n"]
    prep = K.prepare_batch(items, pad_to=32, native=native)
    a = R.expand_np(prep.buf)
    ints = {name: R.column_ints(prep.buf, j * K.HALF_WORDS, K.HALF_WORDS)
            for j, name in enumerate(R.HALVES)}
    ints.update({name: R.column_ints(
        prep.buf, K.FIELD_ROW0 + j * K.FIELD_WORDS, K.FIELD_WORDS)
        for j, name in enumerate(R.FIELDS)})
    for i, item in enumerate(items):
        q, z, r, s = item[:4]
        assert a["host_valid"][i]
        tag = item[4] if len(item) > 4 else None
        assert a["schnorr"][i] == (tag == "schnorr")
        assert a["bip340"][i] == (tag == "bip340")
        if tag is None:
            w = pow(s, -1, CURVE_N)
            u1, u2 = z * w % CURVE_N, r * w % CURVE_N
        else:
            u1, u2 = s, (CURVE_N - z) % CURVE_N
        half = {n: (-1 if a["n" + n[1:]][i] else 1) * ints[n][i]
                for n in R.HALVES}
        assert (half["d1a"] + half["d1b"] * K.LAMBDA) % CURVE_N == u1
        assert (half["d2a"] + half["d2b"] * K.LAMBDA) % CURVE_N == u2
        assert (ints["qx"][i], ints["qy"][i], ints["r1"][i]) == (q.x, q.y, r)
        second = tag is None and r + CURVE_N < CURVE_P
        assert a["r2_valid"][i] == second
        assert ints["r2"][i] == (r + CURVE_N if second else 0)
    n = len(items)
    assert a["r2_valid"][n - 3:n].tolist() == [True, False, False]


# --- the expansion: numpy reference against the scalar definitions ------------

HALF_EDGES = [0, 1, 0xF, 1 << 31, 1 << 32, (1 << 64) - 1, 1 << 64, 1 << 128,
              (1 << 131) | 1, (1 << 132) - 1]
FIELD_EDGES = [0, 1, F.MASK, 1 << 11, (1 << 32) - 1, 1 << 32, 1 << 253,
               CURVE_P - 1, CURVE_N - 1, CURVE_N, CURVE_P - CURVE_N - 1,
               (1 << 256) - 1]


def _random_buffer(size: int, seed: int) -> tuple:
    r = random.Random(seed)
    halves = {n: HALF_EDGES + [r.getrandbits(132) for _ in range(size - 10)]
              for n in R.HALVES}
    fields = {n: FIELD_EDGES + [r.getrandbits(256) for _ in range(size - 12)]
              for n in R.FIELDS}
    for vals in (*halves.values(), *fields.values()):
        r.shuffle(vals)
    flags = {n: [r.random() < 0.5 for _ in range(size)] for n in K.FLAG_NAMES}
    return R.pack(halves, fields, flags, size), halves, fields, flags


def test_np_conversions_match_scalar():
    vals = FIELD_EDGES + [rng.getrandbits(256) for _ in range(50)]
    for v, row in zip(vals, R.ints_to_limbs_np(vals)):
        assert (row == F.to_limbs(v)).all()
    dvals = HALF_EDGES + [rng.getrandbits(132) for _ in range(50)]
    for v, row in zip(dvals, R.ints_to_digits_np(dvals)):
        assert row.tolist() == R.digits_base16(v)


@pytest.mark.parametrize("size", [16, 40])
def test_numpy_expansion_is_digits_limbs_and_masks(size):
    buf, halves, fields, flags = _random_buffer(size, seed=size)
    a = R.expand_np(buf)
    for n in R.HALVES:
        assert a[n].shape == (K.WINDOWS, size) and a[n].dtype == np.int32
        assert np.array_equal(a[n].T, R.ints_to_digits_np(halves[n]))
    for n in R.FIELDS:
        assert a[n].shape == (F.NLIMBS, size) and a[n].dtype == np.int32
        assert np.array_equal(a[n].T, R.ints_to_limbs_np(fields[n]))
    for n in K.FLAG_NAMES:
        assert a[n].dtype == bool and a[n].tolist() == flags[n]


@pytest.mark.parametrize("reader", ["reference", "jitted"])
def test_every_flag_combination(reader):
    """256 columns, one a combination: each mask reads its own bit, in the
    reference and in the program, and the host's ``schnorr_free`` is the
    two algorithm bits'."""
    want = {n: [bool(bits >> b & 1) for bits in range(256)]
            for b, n in enumerate(K.FLAG_NAMES)}
    buf = R.pack({}, {}, want, 256)
    assert buf[K.FLAGS_ROW].tolist() == list(range(256))
    if reader == "reference":
        got = R.expand_np(buf)
    else:
        got = dict(zip((n for n, _ in R.DEVICE_FIELDS), _expand_jit(buf)))
    for n in K.FLAG_NAMES:
        assert np.asarray(got[n]).tolist() == want[n], n
    for bits in range(256):
        one = K.PreparedBatch(buf[:, bits:bits + 1], 1)
        assert one.schnorr_free == (not (want["schnorr"][bits]
                                         or want["bip340"][bits]))


# --- the expansion: the jitted one against the numpy one ----------------------

_expand_jit = jax.jit(K.expand_lane)


def _assert_device_equals_reference(buf: np.ndarray) -> None:
    got = _expand_jit(jnp.asarray(buf))
    want = R.device_args_np(buf)
    assert len(got) == len(want) == 16
    for (name, nd), g, w in zip(R.DEVICE_FIELDS, got, want):
        g = np.asarray(g)
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert g.ndim == nd, name
        assert np.array_equal(g, w), name


@pytest.mark.parametrize("size", [1, 12, 40, 256])
def test_jitted_expansion_equals_numpy_expansion_random(size):
    if size < 12:
        buf = R.pack({n: [(1 << 132) - 1] for n in R.HALVES},
                     {n: [(1 << 256) - 1] for n in R.FIELDS},
                     {n: [True] for n in K.FLAG_NAMES}, size)
    else:
        buf = _random_buffer(size, seed=1000 + size)[0]
    _assert_device_equals_reference(buf)


@pytest.mark.parametrize("name", list(EDGE_BATCHES))
def test_jitted_expansion_equals_numpy_expansion_prepared(name):
    items = EDGE_BATCHES[name]
    prep = K.prepare_batch(items, pad_to=32, native=False)
    _assert_device_equals_reference(prep.buf)


def test_expansion_order_is_verify_cores_signature():
    """What ``device_args``' order and ``ARG_IS_2D`` pinned while the host
    made sixteen arrays: the expansion yields verify_core's arguments by
    position, digits and limbs 2-D with the batch trailing, masks 1-D —
    for the Pallas body too."""
    from tpunode.verify.pallas_kernel import verify_blocked_impl

    names = [n for n, _ in R.DEVICE_FIELDS]
    assert list(inspect.signature(K.verify_core).parameters) == names
    assert list(inspect.signature(verify_blocked_impl).parameters)[:16] == names
    out = jax.eval_shape(K.expand_lane,
                         jax.ShapeDtypeStruct((K.ROWS, 64), jnp.int32))
    rows = {"d": K.WINDOWS, "q": F.NLIMBS, "r": F.NLIMBS}
    for (name, nd), o in zip(R.DEVICE_FIELDS, out):
        if nd == 2:
            assert o.shape == (rows[name[0]], 64) and o.dtype == jnp.int32
        else:
            assert o.shape == (64,) and o.dtype == jnp.bool_


def test_the_programs_take_one_buffer():
    from tpunode.verify.pallas_kernel import _verify_blocked_jit

    for fn in (K._verify_device_jit, _verify_blocked_jit):
        params = inspect.signature(fn).parameters.values()
        positional = [p for p in params if p.kind is not p.KEYWORD_ONLY]
        assert [p.name for p in positional] == ["buf"]
    assert not hasattr(K, "ARG_IS_2D") and not hasattr(K, "_DEVICE_FIELDS")
    assert not hasattr(K.PreparedBatch, "device_args")


# --- schnorr_free: the one derivation, from the flag row ----------------------


@pytest.mark.parametrize("native", [False, pytest.param(True, marks=needs_native)])
@pytest.mark.parametrize("kinds,free", [("mixed", False), ("ecdsa", True),
                                        ("schnorr", False)])
def test_schnorr_free_agrees_with_the_item_tags(kinds, free, native):
    items = _batch(kinds, 9) + [(None, 1, 1, 1)]
    prep = K.prepare_batch(items, pad_to=16, native=native)
    tagged = any(len(it) > 4 for it in items)
    old = not (R.flag(prep.buf, "schnorr").any()
               or R.flag(prep.buf, "bip340").any())
    assert prep.schnorr_free == old == (not tagged) == free


def test_one_tagged_lane_anywhere_clears_schnorr_free():
    for pos, kind in itertools.product((0, 7, 15), ("schnorr", "bip340")):
        items = _batch("ecdsa", 16)
        items[pos] = _signed(kind, pos)
        assert not K.prepare_batch(items, pad_to=16).schnorr_free
    # a tagged item refused by inspection carries no flag: the lane is dead
    # either way, and the pruned program fails a flagged lane closed
    refused = _batch("ecdsa", 4) + [(Q, 9, CURVE_P, 1, "schnorr")]
    assert K.prepare_batch(refused, pad_to=8).schnorr_free


# --- counters: one host-to-device call a dispatch ------------------------------


class _Calls:
    """Counts host-to-device entry points while a dispatch runs."""

    def __init__(self, monkeypatch):
        self.n = 0
        for mod, name in ((jnp, "asarray"), (jax, "device_put")):
            monkeypatch.setattr(mod, name, self._counting(getattr(mod, name)))

    def _counting(self, fn):
        def wrapped(*a, **k):
            self.n += 1
            return fn(*a, **k)
        return wrapped


def _counters() -> tuple:
    return (metrics.get("verify.transfers"),
            metrics.get("verify.transfer_bytes"))


@pytest.mark.parametrize("pad", [4096, 32768])
def test_a_dispatch_is_one_transfer_of_rows_x_4_x_pad_bytes(pad, monkeypatch):
    """Both lane shapes, off the chip: the program is stubbed (the XLA
    program at these widths takes minutes to compile here), the transfer
    is real."""
    seen = []
    monkeypatch.setattr(
        K, "verify_device",
        lambda buf: seen.append(buf) or jnp.zeros(buf.shape[1], bool))
    raw = pack_items(_batch("mixed", 5))
    calls = _Calls(monkeypatch)
    before = _counters()
    out, count = K.dispatch_batch_tpu_raw(raw, pad_to=pad)
    after = _counters()
    assert calls.n == 1
    assert after[0] - before[0] == 1
    assert after[1] - before[1] == K.ROWS * 4 * pad == 212 * pad
    assert count == 5 and out.shape == (pad,)
    (buf,) = seen
    assert isinstance(buf, jax.Array) and buf.shape == (K.ROWS, pad)
    assert buf.dtype == jnp.int32


def test_a_sharded_dispatch_is_one_transfer_too(monkeypatch):
    from tpunode.verify import multichip as MC

    mesh = MC.make_mesh(2)
    monkeypatch.setattr(
        MC, "sharded_verify_fn",
        lambda mesh, kernel, schnorr_free: lambda buf: (
            jnp.zeros(buf.shape[1], bool), 0))
    calls = _Calls(monkeypatch)
    before = _counters()
    ok, count = MC.dispatch_raw_sharded(
        pack_items(_batch("ecdsa", 5)), mesh, pad_to=64, kernel="xla")
    after = _counters()
    assert calls.n == 1
    assert after[0] - before[0] == 1
    assert after[1] - before[1] == K.ROWS * 4 * 64
    assert count == 5 and ok.shape == (64,)
