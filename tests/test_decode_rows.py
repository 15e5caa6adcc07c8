"""The multi-token decode body that reads the KV cache and carries only the
launch's new rows (``TransformerLM.decode_rows``).

* equivalence — T ``decode_rows`` steps give the tokens and the rows
  written of T scanned ``decode_step`` calls on the same cache, and the
  cache they leave gives the next step's logits, bit for bit in float32
  and bfloat16, mid-cache and where the final writes clamp at the
  cache's end;
* structure — no T-step loop of the paged launch's decode body carries
  anything as large as one slot's cache, so the per-token cache copy
  cannot return;
* the ``decode_body``/``decode_launches`` counters of ``kv.stats()``;
* the paged launch that attends the pages in place through the paged
  attention kernel (interpret mode here) against the dense backend.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.models import get_model
from repro.models.attention import gather_block_table
from repro.runtime import kv as kv_mod
from repro.runtime.server import ContinuousBatchingServer, Request
from repro.runtime.traffic import TrafficSpec, generate, replay

S, T = 32, 4


def _carry_reference(model, params, state, tok):
    """T scanned ``decode_step`` calls: the body the rows path replaces."""
    def body(carry, _):
        st, t = carry
        st, logits = model.decode_step(params, st, t)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(t.dtype)
        return (st, nxt), nxt[0, 0]
    (state, nxt), toks = jax.lax.scan(body, (state, tok), None, length=T)
    return state, toks, nxt


def _both_bodies(arch, length, dtype):
    """Prefill ``length`` tokens, then run T steps both ways on that cache.

    Returns (reference state, its toks and nxt), (rows body's toks, nxt,
    rows_k, rows_v), the window's start, and next-step logits from each
    body's resulting cache (the rows written back at the start)."""
    cfg = dataclasses.replace(SMOKE_ARCHS[arch], param_dtype=dtype)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, length), 0,
                                cfg.vocab_size)
    state, logits = jax.jit(model.prefill, static_argnums=2)(
        params, prompt, S)
    tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    ref = jax.jit(lambda p, st, t: _carry_reference(model, p, st, t))(
        params, state, tok)
    rows = jax.jit(lambda p, k, v, n, t: model.decode_rows(p, k, v, n, t, T))(
        params, state["k"], state["v"], state["length"], tok)
    start = min(length, S - T)
    _, _, rk, rv = rows
    at = (0, 0, start, 0, 0)
    written = {"k": jax.lax.dynamic_update_slice(state["k"], rk[:, None], at),
               "v": jax.lax.dynamic_update_slice(state["v"], rv[:, None], at),
               "length": state["length"] + T}
    step = jax.jit(model.decode_step)
    if length + T < S:     # a next step has room only short of the cache end
        nxt_logits = (step(params, ref[0], ref[2])[1],
                      step(params, written, rows[1])[1])
    else:
        nxt_logits = None
    return ref, rows, start, nxt_logits


@pytest.mark.parametrize("length", [9, S - T + 1], ids=["mid", "clamped"])
@pytest.mark.parametrize("arch", ["gemma-2b", "qwen3-8b", "deepseek-7b",
                                  "qwen2-moe-a2.7b"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_rows_bit_identical_to_scanned_decode_steps(dtype, arch,
                                                          length):
    """Both bodies attend the same cache positions through one attention
    body, so they agree bit for bit, not to a tolerance: each row past
    the first layer carries the previous layer's attention output, so
    equal rows mean the attention gave the same bits.  (Two partial sums
    over cache and window already differ in float32's last bits here.)"""
    (ref_state, ref_toks, ref_nxt), (toks, nxt, rk, rv), start, nxt_logits = \
        _both_bodies(arch, length, dtype)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_toks))
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(ref_nxt))
    f32 = lambda a: np.asarray(a.astype(jnp.float32))      # noqa: E731
    for rows, cache in ((rk, ref_state["k"]), (rv, ref_state["v"])):
        np.testing.assert_array_equal(
            f32(rows), f32(cache[:, 0, start:start + T]))
    if nxt_logits is not None:
        np.testing.assert_array_equal(f32(nxt_logits[1]), f32(nxt_logits[0]))


def _scan_carries(jaxpr, inside=False):
    """(length, carry avals) of every scan under the ``decode_slot`` scope."""
    for e in jaxpr.eqns:
        here = inside or "decode_slot" in str(e.source_info.name_stack)
        if here and e.primitive.name == "scan":
            n0 = e.params["num_consts"]
            yield e.params["length"], [
                v.aval for v in e.invars[n0:n0 + e.params["num_carry"]]]
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scan_carries(sub, here)


def test_paged_decode_scan_carries_no_cache():
    cfg = SMOKE_ARCHS["gemma-2b"]
    eng = ContinuousBatchingServer(cfg, batch_size=2, max_seq=64,
                                   tokens_per_launch=T, seed=0, kv="paged",
                                   kv_page_tokens=8)
    kv = eng.kv
    jaxpr = jax.make_jaxpr(kv._decode_slots.__wrapped__)(
        eng.params, kv.k_pool, kv.v_pool,
        jnp.zeros((2, kv.n_blk), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 1, 1), jnp.int32))
    slot_cache = cfg.n_layers * 64 * cfg.n_kv_heads * cfg.hd
    token_loops = [c for n, c in _scan_carries(jaxpr.jaxpr) if n == T]
    assert token_loops, "no T-step scan under decode_slot"
    for carry in token_loops:
        assert max(a.size for a in carry) < slot_cache, [
            a.shape for a in carry]


def _served_stats(arch, **kw):
    eng = ContinuousBatchingServer(SMOKE_ARCHS[arch], batch_size=2,
                                   max_seq=32, tokens_per_launch=T, seed=0,
                                   **kw)
    spec = TrafficSpec(n_requests=3, rate=1000.0, prompt_lens=(4, 8),
                       new_tokens=(6,), seed=1)
    _, metrics = replay(eng, generate(spec, SMOKE_ARCHS[arch].vocab_size),
                        realtime=False)
    return metrics["kv"]


@pytest.mark.parametrize("arch,kw,body", [
    ("gemma-2b", {"kv": "paged", "kv_page_tokens": 8}, "rows"),
    ("gemma-2b", {}, "rows"),
    ("mamba2-780m", {}, "carry"),
], ids=["paged", "dense", "dense-ssm"])
def test_kv_stats_report_decode_body(arch, kw, body):
    stats = _served_stats(arch, **kw)
    assert stats["decode_body"] == body
    assert stats["decode_launches"] > 0


def _first_launch(cfg, prompts, **kw):
    """An engine after one step: every prompt admitted and prefilled, then
    one decode launch, with one slot left empty.  Slots are not released,
    so a request that the launch finished keeps its state to compare."""
    eng = ContinuousBatchingServer(cfg, batch_size=len(prompts) + 1,
                                   max_seq=S, tokens_per_launch=T, seed=0,
                                   **kw)
    eng.kv.release = lambda slot: None
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=2 * T))
    eng.step()
    assert eng.kv.counters.decode_launches == 1
    return eng


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma-2b"])
def test_paged_kernel_launch_matches_dense(monkeypatch, arch):
    """With the path function set to the kernel, the paged launch reads
    the pages in place.  Its tokens equal the dense launch's, and so do
    the next step's logits from the state each launch leaves (in float32,
    to 1e-4): those depend on the K/V rows the launch wrote, each of which
    past the first layer carries that launch's attention output."""
    cfg = dataclasses.replace(SMOKE_ARCHS[arch], param_dtype="float32")
    rs = np.random.default_rng(4)
    # the last prompt ends past S - T: its window starts at the clamp
    prompts = [list(rs.integers(1, cfg.vocab_size, size=n))
               for n in (5, 13, S - T + 2)]
    dense = _first_launch(cfg, prompts)
    monkeypatch.setattr(kv_mod, "_attends_pages_in_place", lambda pool: True)
    paged = _first_launch(cfg, prompts, kv="paged", kv_page_tokens=8)
    kv = paged.kv
    assert kv.stats()["decode_body"] == "pages"
    for a, b in zip(dense.tickets, paged.tickets):
        assert a.tokens == b.tokens
    model, step = dense.model, jax.jit(dense.model.decode_step)
    for slot in range(len(prompts)):
        d = jax.tree_util.tree_map(lambda x: x[slot], dense.kv._slots)
        table = jnp.asarray(kv.tables[slot])
        p = {"k": gather_block_table(kv.k_pool, table),
             "v": gather_block_table(kv.v_pool, table),
             "length": jnp.asarray(kv.lengths[slot], jnp.int32)}
        assert int(d["length"]) == int(p["length"])
        np.testing.assert_allclose(
            np.asarray(step(paged.params, p, kv._nxt[slot])[1]),
            np.asarray(step(dense.params, d, dense.kv._nxt[slot])[1]),
            rtol=1e-4, atol=1e-4)
    stats = kv.stats()
    starts = [min(len(p), S - T) for p in prompts]
    assert stats["decode_pages_read"] == sum(-(-n // 8) for n in starts)
    assert stats["decode_pages_table"] == len(prompts) * kv.n_blk
    # the empty slot attended its window alone and wrote finite rows to
    # the scratch page
    assert np.isfinite(np.asarray(kv.k_pool[:, 0])).all()
    assert np.isfinite(np.asarray(kv.v_pool[:, 0])).all()
