"""`ops.attention.slot_ring_reader`: a burst's window layers read the
rings of the call's lanes and of no other slot where the call is narrow
against the slots (PR 58), every slot's in place where it is most of them.
Each of the three `attend`s is held to a plain float32 reference a lane
(its own ring, the rows `ring_seen` says it sees), with the lanes' slots
out of order, idle lanes at the null slot, lanes shorter than the window
and a lane that has wrapped; the two paths to each other; and the lowered
burst to the shapes the rule promises."""
import functools
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ray_tpu.models import configs, decoding, init_params  # noqa: E402
from ray_tpu.ops import attention  # noqa: E402

WINDOW, RING, LAYERS = 6, 8, 2       # a ring is a window and a chunk of 2
HKV, REP, D = 2, 2, 8                # KV heads (groups), query heads each
E = HKV * 2 * D                      # a differential head's flat row
W, D_V, SCALE = 24, 16, 0.25         # a latent row, its value columns


def _softmax_mix(scores, seen, values):
    """Rows `seen` of `values` (R, C) mixed by the soft-max of `scores`
    (R,), in float64."""
    sc = np.where(seen, scores.astype(np.float64), -np.inf)
    p = np.exp(sc - sc.max())
    return (p / p.sum()) @ values.astype(np.float64)


def _plain_gqa(q, k_ring, v_ring, seen):
    """q (H, D) over one ring (R, Hkv, D): (H, D)."""
    out = np.zeros(q.shape)
    for h in range(q.shape[0]):
        g = h // REP
        out[h] = _softmax_mix(k_ring[:, g] @ q[h] * D ** -0.5, seen,
                              v_ring[:, g])
    return out


def _plain_diff(q6, k_ring, v_ring, seen):
    """q6 (G, rep, 2, D) over one ring of flat rows (R, E): map c of head
    (g, r) scores half c of group g's key and mixes the group's whole
    value, (G, rep, 2, 2D)."""
    out = np.zeros(q6.shape[:3] + (2 * D,))
    for g in range(HKV):
        for r in range(REP):
            for c in range(2):
                at = g * 2 * D + c * D
                out[g, r, c] = _softmax_mix(
                    k_ring[:, at:at + D] @ q6[g, r, c] * D ** -0.5, seen,
                    v_ring[:, g * 2 * D:(g + 1) * 2 * D])
    return out


def _plain_latent(q, ring, _, seen):
    """q (H, W) over one ring of latent rows (R, W): (H, d_v)."""
    return np.stack([_softmax_mix(ring @ q[h] * SCALE, seen, ring[:, :D_V])
                     for h in range(q.shape[0])])


ATTENDS = {
    "gqa": (attention.window_attention, _plain_gqa,
            (HKV * REP, D), (HKV, D)),
    "diff": (attention.window_diff_attention, _plain_diff,
             (HKV, REP, 2, D), (E,)),
    "latent": (functools.partial(attention.latent_window_attention,
                                 d_v=D_V, scale=SCALE), _plain_latent,
               (HKV * REP, W), (W,)),
}


def _case(kind, lanes, n_slots, seed=0):
    """A call of `lanes` lanes on `n_slots` slots (the null slot last):
    the last lane idle, the slots of the others drawn out of order, one
    lane shorter than the window, one that has wrapped its ring twice."""
    _, _, q_shape, row = ATTENDS[kind]
    rng = np.random.default_rng(seed + 131 * lanes + n_slots)
    slots = rng.permutation(n_slots - 1)[:lanes].astype(np.int32)
    kv_len = rng.integers(1, 3 * RING, lanes).astype(np.int32)
    kv_len[0] = WINDOW - 2
    if lanes > 2:
        kv_len[1] = 2 * RING + 3
    slots[-1], kv_len[-1] = n_slots - 1, 0
    positions = np.maximum(kv_len - 1, 0)[:, None].astype(np.int32)
    q = rng.standard_normal((lanes, 1) + q_shape).astype(np.float32)
    k_rings = rng.standard_normal(
        (LAYERS, n_slots, RING) + row).astype(np.float32)
    v_rings = k_rings if kind == "latent" else rng.standard_normal(
        k_rings.shape).astype(np.float32)
    return slots, positions, kv_len, q, k_rings, v_rings


def _read(kind, case, layer):
    """The reader's answers, (S, 1, ..)."""
    def call(slots, positions, kv_len, q, k_rings, v_rings, layer):
        read = attention.slot_ring_reader(
            ATTENDS[kind][0], slots, positions, kv_len, WINDOW,
            k_rings.shape[1])
        return read(q, k_rings, v_rings, layer)
    return np.asarray(jax.jit(call)(*case, jnp.int32(layer)))


def _force(monkeypatch, lanes_rings: bool):
    """Every call traced from here on takes the one path."""
    monkeypatch.setattr(attention, "_lanes_rings",
                        lambda lanes, n_slots: lanes_rings)


SIZES = [(lanes, n_slots) for n_slots in (9, 33) for lanes in (2, 4, 8)]


@pytest.mark.parametrize("lanes,n_slots", SIZES)
@pytest.mark.parametrize("kind", list(ATTENDS))
def test_a_burst_reads_each_lane_its_own_ring(kind, lanes, n_slots):
    case = _case(kind, lanes, n_slots)
    slots, positions, kv_len, q, k_rings, v_rings = case
    got = _read(kind, case, layer=1)
    assert got.shape[0] == lanes
    seen = np.asarray(attention.ring_seen(
        jnp.asarray(positions), jnp.asarray(kv_len), RING, WINDOW))
    plain = ATTENDS[kind][1]
    for j in range(lanes - 1):          # the last lane is idle
        want = plain(q[j, 0], k_rings[1, slots[j]], v_rings[1, slots[j]],
                     seen[j, 0])
        assert seen[j, 0].sum() == min(kv_len[j], WINDOW)
        np.testing.assert_allclose(got[j, 0].reshape(want.shape), want,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lanes,n_slots", SIZES)
@pytest.mark.parametrize("kind", list(ATTENDS))
def test_the_two_paths_agree(kind, lanes, n_slots, monkeypatch):
    """The lanes' rings joined against every slot's in place: the same
    rows under the same queries, on the CPU to the bit."""
    case = _case(kind, lanes, n_slots, seed=7)
    _force(monkeypatch, True)
    joined = _read(kind, case, layer=0)
    _force(monkeypatch, False)
    in_place = _read(kind, case, layer=0)
    np.testing.assert_array_equal(joined[:-1], in_place[:-1])


@pytest.mark.parametrize("lanes,n_slots,ring_slots", [
    (1, 33, 1), (4, 33, 4), (8, 33, 8), (16, 33, 16), (32, 33, 33),
    (4, 9, 4), (8, 9, 9), (2, 3, 3)])
def test_the_rule_reads_only_the_calls_shapes(lanes, n_slots, ring_slots):
    assert attention.ring_slots_read(lanes, n_slots) == ring_slots


def _lowered_burst(width, num_slots, num_blocks=33):
    cfg = configs.get("tiny-window-moe")
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: decoding.init_sequence_state(
        cfg, num_blocks, 16, num_slots=num_slots, prefill_chunk=64))
    _, burst, _ = decoding.make_paged_engine_fns(cfg)

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    return burst.lower(
        shapes, cache, arr(width), arr(width, 16), arr(width),
        arr(width, dtype=jnp.bool_), arr(width, dtype=jnp.float32),
        jax.eval_shape(lambda: jax.random.key(0)), n_steps=8,
        slots=arr(width)).as_text()


def _dot_operand_dims(text):
    dims = set()
    for line in text.splitlines():
        if "dot_general" in line:
            for shape in re.findall(r"tensor<([0-9x]+)x[a-z]", line):
                dims.update(int(d) for d in shape.split("x"))
    return dims


def test_a_narrow_burst_multiplies_no_array_of_every_slot(monkeypatch):
    """Width 4 on 32 slots and the null slot: no operand or result of a
    product in the lowered burst has a dimension of 33 (the pool has 35
    blocks: nothing else is 33 long)."""
    dims = _dot_operand_dims(_lowered_burst(4, 32, num_blocks=35))
    assert dims and 33 not in dims
    # and the in-place path, forced, shows what the assertion looks for
    _force(monkeypatch, False)
    assert 33 in _dot_operand_dims(_lowered_burst(4, 32, num_blocks=35))


def test_a_burst_as_wide_as_the_slots_lowers_as_at_the_parent():
    """Width 8 on 8 slots and the null slot keeps the in-place read: the
    digest `tests/test_block_diffusion_serving.py` took of this program
    on PR 51's tree (the same shapes) still holds."""
    digest = hashlib.sha256(_lowered_burst(8, 8).encode()).hexdigest()[:16]
    assert digest == "f1575631141e1dd7"


@pytest.mark.parametrize("name,num_slots,want", [
    ("tiny-window-moe", 8, 4),      # width 4 of 9: the lanes' rings
    ("tiny-window-moe", 4, 5),      # width 4 of 5: every slot's, in place
    ("tiny", 8, 0)])                # no rings
def test_the_tick_log_says_how_many_slots_rings_a_burst_read(
        name, num_slots, want):
    from ray_tpu.serve.llm import TICK_FIELDS, PagedLLMEngine

    cfg = configs.get(name)
    e = PagedLLMEngine(cfg, init_params(jax.random.key(3), cfg),
                       num_slots=num_slots, max_len=128, block_size=8,
                       prefill_chunk=16, max_burst=4)
    try:
        e.generate(list(range(1, 20)), max_tokens=9)
        stats = e.engine_stats()
    finally:
        e.shutdown()
    assert stats["tick_fields"] == TICK_FIELDS
    assert TICK_FIELDS[-1] == "ring_slots"
    ticks = [dict(zip(TICK_FIELDS, t)) for t in stats["tick_log"]]
    bursts = [t for t in ticks if t["width"]]
    assert bursts and {t["ring_slots"] for t in bursts} == {want}
    assert {t["ring_slots"] for t in ticks if not t["width"]} <= {0}
