"""The engine's multi-token decode body, which attends the KV pages in place
and carries only the launch's new rows (``TransformerLM.decode_rows_paged``).

* equivalence — T steps of the paged body against T scanned
  ``decode_step`` calls on the same cache, mid-cache and where the final
  writes clamp at the cache's end: in float32 the tokens are equal and the
  rows, the first step's logits and the next step's logits agree to 1e-4;
  in bfloat16 the first step's logits and rows agree to 2^-4 (the paged
  attention kernel's float32 online softmax rounds differently from
  ``decode_step``'s bfloat16 probabilities, so later greedy tokens may
  part);
* structure — no T-step loop of the decode launch carries anything as
  large as one slot's cache, so the per-token cache copy cannot return;
* the engine's first decode launch against one-shot ``Server.serve`` and
  ``decode_step``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.models import get_model
from repro.models.attention import gather_block_table
from repro.models.layers import embed, rms_norm, unembed
from repro.models.transformer import block_decode_paged
from repro.runtime.server import ContinuousBatchingServer, Request, Server

S, T, PT = 32, 4, 8
BF16_TOL = 2.0 ** -4


def _carry_reference(model, params, state, tok):
    """T scanned ``decode_step`` calls: what the paged body must match.
    Returns (state, toks [T], nxt [1, 1], logits of each step [T, V])."""
    def body(carry, _):
        st, t = carry
        st, logits = model.decode_step(params, st, t)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(t.dtype)
        return (st, nxt), (nxt[0, 0], logits[0, 0])
    (state, nxt), (toks, logits) = jax.lax.scan(
        body, (state, tok), None, length=T)
    return state, toks, nxt, logits


def _paged_first_logits(model, params, k_pool, v_pool, tables, lengths, tok):
    """The logits of the paged body's first token step: its window and its
    layer scan over the model's paged block, once."""
    cfg = model.cfg
    L, _, pt = k_pool.shape[:3]
    start = jnp.minimum(lengths, tables.shape[1] * pt - T)
    at = start[:, None] + jnp.arange(T)[None, :]
    page = jnp.take_along_axis(tables, at // pt, axis=1)

    def layer(x, inp):
        lp, rk, rv, i = inp
        x, _, _ = block_decode_paged(lp, cfg, x, k_pool, v_pool, tables, i,
                                     rk, rv, start, lengths)
        return x, None

    x, _ = jax.lax.scan(layer, embed(params["emb"], tok, cfg.embed_scale), (
        params["layers"], k_pool[:, page, at % pt], v_pool[:, page, at % pt],
        jnp.arange(L)))
    return unembed(params["emb"], rms_norm(params["final_norm"], x))


def _both_bodies(arch, length, dtype):
    """Prefill ``length`` tokens, then run T steps both ways on that cache:
    T ``decode_step`` calls, and the paged body over the cache cut into
    pages (page 0 the scratch page, the slot's table 1..S/PT).

    Returns the reference (state, toks, nxt, logits), the paged body's
    (toks, nxt, rows_k, rows_v) and first-step logits, the window's start,
    and next-step logits from each body's resulting cache (the rows
    written back at the start)."""
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
    L, _, _, Hk, hd = state["k"].shape

    def pool(cache):
        pages = cache[:, 0].reshape(L, S // PT, PT, Hk, hd)
        return jnp.concatenate([jnp.zeros_like(pages[:, :1]), pages], 1)

    args = (params, pool(state["k"]), pool(state["v"]),
            jnp.arange(1, S // PT + 1, dtype=jnp.int32)[None],
            state["length"][None], tok)
    paged = jax.jit(lambda *a: model.decode_rows_paged(*a, T))(*args)
    first = jax.jit(lambda *a: _paged_first_logits(model, *a))(*args)
    start = min(length, S - T)
    _, nxt, rk, rv = paged
    at = (0, 0, start, 0, 0)
    written = {"k": jax.lax.dynamic_update_slice(state["k"], rk, at),
               "v": jax.lax.dynamic_update_slice(state["v"], rv, at),
               "length": state["length"] + T}
    step = jax.jit(model.decode_step)
    if length + T < S:     # a next step has room only short of the cache end
        nxt_logits = (step(params, ref[0], ref[2])[1],
                      step(params, written, nxt)[1])
    else:
        nxt_logits = None
    return ref, paged, first[0, 0], start, nxt_logits


@pytest.mark.parametrize("length", [9, S - T + 1], ids=["mid", "clamped"])
@pytest.mark.parametrize("arch", ["gemma-2b", "qwen3-8b", "deepseek-7b",
                                  "qwen2-moe-a2.7b"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_rows_bit_identical_to_scanned_decode_steps(dtype, arch,
                                                          length):
    """The paged body attends the pages through the kernel, merged with
    the window by log-sum-exp in float32; ``decode_step`` attends the
    whole cache.  Each row past the first layer carries the previous
    layer's attention output, so agreeing rows mean the attention agreed.
    In float32 the two differ in the last bits only (1e-6 here), so the
    whole T-step chain must agree.  In bfloat16 the two round the
    attention output at different points, about one bfloat16 step of the
    values (2^-5 at magnitudes 4 to 8), so only the first step is held,
    to 2^-4: a misplaced row or mask moves values by order one."""
    (ref_state, ref_toks, ref_nxt, ref_logits), (toks, nxt, rk, rv), \
        first, start, nxt_logits = _both_bodies(arch, length, dtype)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))      # noqa: E731
    # the first-step logits follow the body's own first token
    assert int(jnp.argmax(first)) == int(toks[0, 0])
    rows = [(rk[:, 0], ref_state["k"][:, 0, start:start + T]),
            (rv[:, 0], ref_state["v"][:, 0, start:start + T])]
    if dtype == "float32":
        np.testing.assert_array_equal(np.asarray(toks[0]),
                                      np.asarray(ref_toks))
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(ref_nxt))
        tol = dict(rtol=1e-4, atol=1e-4)
    else:
        # the window up to and including the first step's row
        w = min(length, S - 1) - start
        rows = [(a[:, :w + 1], b[:, :w + 1]) for a, b in rows]
        tol = dict(rtol=0, atol=BF16_TOL)
    for got, want in rows:
        np.testing.assert_allclose(f32(got), f32(want), **tol)
    np.testing.assert_allclose(f32(first), f32(ref_logits[0]), **tol)
    if dtype == "float32" and nxt_logits is not None:
        np.testing.assert_allclose(f32(nxt_logits[1]), f32(nxt_logits[0]),
                                   **tol)


def _scan_carries(jaxpr, inside=False):
    """(length, carry avals) of every scan under the ``decode_pages``
    scope."""
    for e in jaxpr.eqns:
        here = inside or "decode_pages" in str(e.source_info.name_stack)
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
                                   tokens_per_launch=T, seed=0,
                                   kv_page_tokens=8)
    kv = eng.kv
    jaxpr = jax.make_jaxpr(kv._decode_slots.__wrapped__)(
        eng.params, kv.k_pool, kv.v_pool,
        jnp.zeros((2, kv.n_blk), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 1, 1), jnp.int32))
    slot_cache = cfg.n_layers * 64 * cfg.n_kv_heads * cfg.hd
    token_loops = [c for n, c in _scan_carries(jaxpr.jaxpr) if n == T]
    assert token_loops, "no T-step scan under decode_pages"
    for carry in token_loops:
        assert max(a.size for a in carry) < slot_cache, [
            a.shape for a in carry]


def _first_launch(cfg, prompts):
    """An engine after one step: every prompt admitted and prefilled, then
    one decode launch, with one slot left empty.  Slots are not released,
    so a request that the launch finished keeps its state to compare."""
    eng = ContinuousBatchingServer(cfg, batch_size=len(prompts) + 1,
                                   max_seq=S, tokens_per_launch=T, seed=0,
                                   kv_page_tokens=PT)
    eng.kv.release = lambda slot: None
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=2 * T))
    eng.step()
    assert eng.kv.decode_launches == 1
    return eng


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma-2b"])
def test_paged_kernel_launch_matches_dense(arch):
    """The engine's first decode launch reads the pages in place through
    the paged attention kernel.  Its tokens equal one-shot
    ``Server.serve``'s, and the next step's logits from the cache it
    leaves equal those from the cache T ``decode_step`` calls leave after
    the same prefill (in float32, to 1e-4): those depend on the K/V rows
    the launch wrote, each of which past the first layer carries that
    launch's attention output."""
    cfg = dataclasses.replace(SMOKE_ARCHS[arch], param_dtype="float32")
    rs = np.random.default_rng(4)
    # the last prompt ends past S - T: its window starts at the clamp
    prompts = [rs.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 13, S - T + 2)]
    eng = _first_launch(cfg, prompts)
    kv, model, params = eng.kv, eng.model, eng.params
    solo = Server(cfg, batch_size=1, max_seq=S, tokens_per_launch=1, seed=0)
    prefill = jax.jit(model.prefill, static_argnums=2)
    carry = jax.jit(lambda p, st, t: _carry_reference(model, p, st, t))
    step = jax.jit(model.decode_step)
    for slot, (prompt, tix) in enumerate(zip(prompts, eng.tickets)):
        ref = Request(tix.uid, prompt, max_new_tokens=len(tix.tokens))
        solo.serve([ref])
        assert tix.tokens == ref.tokens
        state, logits = prefill(params, jnp.asarray(prompt[None]), S)
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        d, _, nxt, _ = carry(params, state, tok)
        np.testing.assert_array_equal(np.asarray(kv._nxt[slot]),
                                      np.asarray(nxt))
        table = jnp.asarray(kv.tables[slot])
        p = {"k": gather_block_table(kv.k_pool, table),
             "v": gather_block_table(kv.v_pool, table),
             "length": jnp.asarray(kv.lengths[slot], jnp.int32)}
        assert int(d["length"]) == int(p["length"])
        np.testing.assert_allclose(
            np.asarray(step(params, p, kv._nxt[slot])[1]),
            np.asarray(step(params, d, nxt)[1]), rtol=1e-4, atol=1e-4)
    stats = kv.stats()
    starts = [min(len(p), S - T) for p in prompts]
    assert stats["decode_pages_read"] == sum(-(-n // PT) for n in starts)
    assert stats["decode_pages_table"] == len(prompts) * kv.n_blk
    # the empty slot attended its window alone and wrote finite rows to
    # the scratch page
    assert np.isfinite(np.asarray(kv.k_pool[:, 0])).all()
    assert np.isfinite(np.asarray(kv.v_pool[:, 0])).all()
