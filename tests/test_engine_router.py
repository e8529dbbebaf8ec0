"""Serving engine (continuous batching) + router + paged KV cache."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as TF
from repro.models.kvcache import PagedKVCache
from repro.serving.engine import InstanceEngine, ServeRequest
from repro.serving.router import Router

CFG = get_config("granite-8b", reduced=True)


def _engine(n_slots=3, max_seq=64):
    params = TF.init_params(jax.random.PRNGKey(0), CFG)
    return InstanceEngine(CFG, params, n_slots=n_slots, max_seq=max_seq)


@pytest.mark.slow
def test_continuous_batching_completes_all_requests():
    eng = _engine(n_slots=3)
    rng = np.random.default_rng(0)
    reqs = [
        ServeRequest(i, rng.integers(0, CFG.vocab_size, size=8).astype(np.int32),
                     max_new_tokens=4 + (i % 3))
        for i in range(7)  # more requests than slots -> queueing + reuse
    ]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_done()
    assert len(done) == 7
    for r in done:
        assert len(r.out_tokens) >= r.max_new_tokens
        assert all(0 <= t < CFG.vocab_size for t in r.out_tokens)


def _sequential(prompts, max_new):
    """Each request's tokens served alone, on a fresh one-slot engine."""
    out = []
    for i, (p, n) in enumerate(zip(prompts, max_new)):
        eng = _engine(n_slots=1)
        eng.submit(ServeRequest(i, p, n))
        (r,) = eng.run_until_done()
        out.append(r.out_tokens)
    return out


@pytest.mark.slow
def test_engine_batched_equals_sequential():
    """Slot interleaving must not change any request's tokens."""
    prompts = [np.arange(5, dtype=np.int32) + i for i in range(3)]
    eng_b = _engine(n_slots=3)
    for i, p in enumerate(prompts):
        eng_b.submit(ServeRequest(i, p, 5))
    batched = {r.rid: r.out_tokens for r in eng_b.run_until_done()}
    assert [batched[i] for i in range(3)] == _sequential(prompts, [5] * 3)


@contextlib.contextmanager
def _explicit_reads_only(monkeypatch):
    """Refuse every implicit device-to-host read and count the explicit
    ones (``jax.device_get`` calls).  The transfer guard refuses implicit
    reads on an accelerator; a CPU array is read without a copy, which the
    guard does not see, so every host read of a ``jax.Array`` (its
    ``_value``, behind ``int()``, ``np.asarray`` and ``jax.device_get``
    alike) is also refused unless a ``jax.device_get`` is under way.
    Yields the shapes of the arrays read explicitly, in order."""
    reads, inside = [], [False]
    device_get, value = jax.device_get, type(jnp.zeros(1))._value

    def counted_get(x):
        reads.append(x.shape)
        inside[0] = True
        try:
            return device_get(x)
        finally:
            inside[0] = False

    def guarded_value(self):
        assert inside[0], "implicit device-to-host read"
        return value.fget(self)

    monkeypatch.setattr(jax, "device_get", counted_get)
    monkeypatch.setattr(type(jnp.zeros(1)), "_value", property(guarded_value))
    with jax.transfer_guard_device_to_host("disallow"):
        yield reads
    monkeypatch.undo()


def test_engine_reads_each_step_in_one_explicit_transfer(monkeypatch):
    """Three slots live at once, a fourth request queued for a freed slot:
    through ``submit``, ``step`` and retirement no device array is read
    implicitly, each prefill and each decode step reads its tokens in one
    ``jax.device_get``, and the tokens are those each request gets alone."""
    prompts = [np.arange(5, dtype=np.int32) + i for i in range(4)]
    max_new = [5, 3, 6, 4]
    eng = _engine(n_slots=3)
    with _explicit_reads_only(monkeypatch) as reads:
        for i, p in enumerate(prompts):
            eng.submit(ServeRequest(i, p, max_new[i]))
        done = eng.step()
        assert len(eng.active) == 3 and len(eng.queue) == 1
        done += eng.run_until_done()
    served = {r.rid: r.out_tokens for r in done}
    # a (1,) read per prefill, a (3,) read per decode step
    assert sorted(set(reads)) == [(1,), (3,)] and reads.count((1,)) == len(prompts)
    assert len(reads) == eng.stats.decode_steps + len(prompts) == eng.stats.host_syncs
    assert eng.stats.admitted == len(prompts) and not eng.active
    assert [served[i] for i in range(4)] == _sequential(prompts, max_new)


def _serve(eng, steps, submits=(), prefill=None):
    """Step ``eng`` ``steps`` times; ``submits`` maps a step index to the
    requests handed in before it, through local admission, or through
    ``prefill.prefill_only`` + ``admit_prefilled`` when ``prefill`` is an
    engine."""
    at = dict(submits)
    for i in range(steps):
        for r in at.get(i, ()):
            if prefill is None:
                eng.submit(r)
            else:
                first, one = prefill.prefill_only(r)
                assert eng.admit_prefilled(r, first, one)
        eng.step()


@pytest.mark.parametrize("admit", ["local", "prefilled"])
def test_reused_slot_serves_as_fresh_after_its_free_cache_ran_past_max_seq(admit):
    """A free slot keeps decoding scratch: once its ``lengths`` has passed
    ``max_seq``, a request spliced into it still gets the tokens it gets on
    a fresh engine, and the other live slot never sees the slot's past."""
    max_seq = 64
    rng = np.random.default_rng(7)
    pa, pb, pc = (rng.integers(0, CFG.vocab_size, size=n).astype(np.int32) for n in (40, 6, 4))
    admit_b, steps = 32, 40  # A (slot 0) is done after 2 steps; C (slot 1) runs on
    prefill = _engine(n_slots=1, max_seq=max_seq) if admit == "prefilled" else None

    eng = _engine(n_slots=2, max_seq=max_seq)
    a, b, c = ServeRequest(0, pa, 3), ServeRequest(1, pb, 6), ServeRequest(2, pc, 45)
    _serve(eng, admit_b, {0: [a, c]})
    assert a.done and a.slot == 0 and c.slot == 1 and 0 not in eng.active
    lengths = np.asarray(eng.caches["layers"]["lengths"])
    assert (lengths[:, 0] > max_seq).all() and (lengths[:, 1] < max_seq).all()
    _serve(eng, steps - admit_b, {0: [b]}, prefill)
    assert b.done and b.slot == 0 and len(c.out_tokens) == 1 + steps

    alone = _engine(n_slots=2, max_seq=max_seq)
    b_alone = ServeRequest(1, pb, 6)
    _serve(alone, 8, {0: [b_alone]})
    assert b.out_tokens == b_alone.out_tokens

    never_a = _engine(n_slots=2, max_seq=max_seq)
    b2, c2 = ServeRequest(1, pb, 6), ServeRequest(2, pc, 45)
    _serve(never_a, admit_b, {0: [c2]})
    _serve(never_a, steps - admit_b, {0: [b2]}, prefill)
    assert c.out_tokens == c2.out_tokens and b.out_tokens == b2.out_tokens


def test_live_scaling_gate():
    eng = _engine()
    assert eng.can_serve_alone()
    eng.set_loaded_layers(1)
    assert not eng.can_serve_alone()
    eng.set_loaded_layers(CFG.n_layers)
    assert eng.can_serve_alone()


def test_router_fcfs_and_slo():
    router = Router()
    r1 = router.submit(10, 5, now=0.0)
    r2 = router.submit(10, 5, now=0.1)
    eng = _engine()
    dispatched = router.dispatch([eng])
    assert [rec.rid for rec, _ in dispatched] == [r1, r2]  # FCFS order
    router.note_first_token(r1, 0.5)
    router.note_first_token(r2, 0.7)
    for t in (0.6, 0.7, 0.8):
        router.note_token(r1, t)
    rep = router.slo_report()
    assert rep.n == 2
    assert rep.mean_ttft == pytest.approx((0.5 + 0.6) / 2)
    assert 0 <= rep.attainment <= 1


def test_router_skips_partially_loaded_engines():
    router = Router()
    router.submit(10, 5, now=0.0)
    loading = _engine()
    loading.set_loaded_layers(1)
    assert router.dispatch([loading]) == []  # work arrives cooperatively
    ready = _engine()
    assert len(router.dispatch([loading, ready])) == 1


def test_slo_five_x_average_ttft_rule():
    """§6.2: a request violates when its TTFT exceeds 5x the workload mean."""
    router = Router()
    rids = [router.submit(10, 5, now=0.0) for _ in range(10)]
    for rid in rids[:9]:
        router.note_first_token(rid, 0.1)
    router.note_first_token(rids[9], 10.0)
    rep = router.slo_report()
    # mean TTFT = (9*0.1 + 10)/10 = 1.09s; 5x = 5.45s -> only the straggler fails
    assert rep.mean_ttft == pytest.approx(1.09)
    assert rep.attainment == pytest.approx(0.9)


def test_slo_five_x_average_tbt_rule():
    """A single decode stall beyond 5x the mean TBT fails that request."""
    router = Router()
    a = router.submit(10, 5, now=0.0)
    b = router.submit(10, 5, now=0.0)
    router.note_first_token(a, 0.1)
    for i in range(1, 20):  # steady 0.1s TBTs
        router.note_token(a, 0.1 + 0.1 * i)
    router.note_first_token(b, 0.1)
    router.note_token(b, 0.2)
    router.note_token(b, 10.2)  # 10s stall >> 5x mean
    rep = router.slo_report()
    assert rep.attainment == pytest.approx(0.5)


def test_handoff_three_steps_and_gap_detection():
    router = Router()
    rid = router.submit(16, 4, now=0.0)
    router.note_first_token(rid, 0.1)
    router.begin_handoff(rid, src=0, dst=1, tokens_frozen=1, now=0.1)
    assert router.pinned(rid) and not router.in_transit(rid)  # step 1: frozen
    router.mark_migrating(rid)
    assert router.in_transit(rid)  # step 2: pages on the wire
    assert router.complete_handoff(rid, tokens_resumed=1, now=0.2)
    assert not router.in_transit(rid) and not router.pinned(rid)  # step 3
    assert router.handoff_report() == (1, 0)
    # a mismatched resume position is a dropped/replayed token
    rid2 = router.submit(16, 4, now=0.3)
    router.begin_handoff(rid2, src=0, dst=1, tokens_frozen=1, now=0.4)
    router.mark_migrating(rid2)
    assert not router.complete_handoff(rid2, tokens_resumed=0, now=0.5)
    assert router.handoff_report() == (2, 1)


def test_dispatch_never_hands_out_pinned_requests():
    router = Router()
    pinned = router.submit(16, 4, now=0.0)
    free = router.submit(16, 4, now=0.1)
    router.begin_handoff(pinned, src=0, dst=1, tokens_frozen=1, now=0.2)
    eng = _engine()
    dispatched = router.dispatch([eng])
    assert [rec.rid for rec, _ in dispatched] == [free]
    assert [r.rid for r in router.queue] == [pinned]  # still queued, untouched


def test_paged_cache_matches_contiguous():
    cache = PagedKVCache(n_blocks=16, block_size=4, n_kv=2, head_dim=8, dtype=np.float32)
    rng = np.random.default_rng(1)
    k = rng.standard_normal((11, 2, 8)).astype(np.float32)
    v = rng.standard_normal((11, 2, 8)).astype(np.float32)
    cache.allocate(0)
    cache.append(0, k[:6], v[:6])
    cache.append(0, k[6:], v[6:])
    kg, vg, length = cache.gather(0, max_seq=16)
    assert length == 11
    np.testing.assert_array_equal(kg[:11], k)
    np.testing.assert_array_equal(vg[:11], v)
    np.testing.assert_array_equal(kg[11:], 0)
    free_before = cache.n_free_blocks
    cache.release(0)
    assert cache.n_free_blocks == free_before + 3  # ceil(11/4) blocks back


def test_paged_cache_oom():
    cache = PagedKVCache(n_blocks=2, block_size=2, n_kv=1, head_dim=4, dtype=np.float32)
    cache.allocate(0)
    with pytest.raises(MemoryError):
        cache.append(0, np.zeros((5, 1, 4), np.float32), np.zeros((5, 1, 4), np.float32))
