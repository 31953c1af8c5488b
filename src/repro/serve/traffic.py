"""Seeded synthetic serving traffic + the solo-jit parity oracle.

Traffic is generated in *tick units*: the engine has no wall-clock of its
own (one :meth:`~repro.serve.scheduler.ServeEngine.step` is one tick), so
Poisson arrivals are exponential inter-arrival gaps measured in ticks and
a request joins the engine when the driver loop reaches its arrival tick.
Prompt and generation lengths are drawn from small discrete mixes — the
ragged-length regime continuous batching exists for (each distinct prompt
length maps to one captured prefill program: length-bucketed admission).

:func:`solo_reference` is the parity oracle AND latency reference: every
request decoded alone, batch-1, on the pre-capture jit path
(:func:`~repro.launch.serve.build_server` + ``decode_stream``) — the
engine's per-request token sequences must match it bit-for-bit under
every policy.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.serve import build_server, decode_stream
from repro.serve.scheduler import Request, ServeEngine, batch_for_prompt


def make_traffic(seed: int, n_requests: int, vocab: int, *,
                 arrival_rate: float = 1.0,
                 prompt_lens: Sequence[int] = (6, 10),
                 gen_lens: Sequence[int] = (5, 9)) -> List[Request]:
    """Poisson arrival stream with mixed prompt/gen lengths, fully seeded.

    ``arrival_rate`` is the expected arrivals per engine tick; the request
    list is sorted by ``arrival_tick`` with ids in arrival order."""
    if arrival_rate <= 0:
        raise ValueError("arrival_rate must be > 0")
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for rid in range(n_requests):
        t += rng.exponential(1.0 / arrival_rate)
        L = int(rng.choice(prompt_lens))
        G = int(rng.choice(gen_lens))
        prompt = rng.integers(0, vocab, size=L).astype(np.int32)
        out.append(Request(req_id=rid, prompt=prompt, gen=G,
                           arrival_tick=int(t)))
    return out


def _warm_engine(engine: ServeEngine, requests: Sequence[Request]) -> None:
    """Compile off the clock, like the oracle's warm-up: one throwaway
    request per distinct prompt length (each length owns a captured
    prefill program) with a decode tick each, then reset the ledger's
    serve counters so the measured run starts clean."""
    rng = np.random.default_rng(0)
    for k, L in enumerate(sorted({r.prompt_len for r in requests})):
        prompt = rng.integers(0, engine.cfg.vocab, size=L).astype(np.int32)
        engine.submit(Request(req_id=-1 - k, prompt=prompt, gen=2))
    engine.drain()
    engine.ledger.reset_timings()


def run_traffic(engine: ServeEngine, requests: Sequence[Request],
                max_ticks: int = 100_000, warmup: bool = True) -> dict:
    """Drive the engine through an arrival stream and measure it.

    Tokens/s counts every emitted token (prefill's first token plus decode
    tokens) over the wall time from first submission to drain.  Per-token
    latency is the gap between consecutive token emissions of one request
    (decode cadence); first-token latency is submission -> first token."""
    if warmup:
        _warm_engine(engine, requests)
    pending = sorted(requests, key=lambda r: (r.arrival_tick, r.req_id))
    i = 0
    t0 = time.perf_counter()
    for tick in range(max_ticks):
        while i < len(pending) and pending[i].arrival_tick <= tick:
            engine.submit(pending[i])
            i += 1
        did = engine.step()
        if not did and i >= len(pending):
            break
    else:
        raise RuntimeError(f"traffic did not drain in {max_ticks} ticks")
    wall_s = time.perf_counter() - t0

    gaps_ms: List[float] = []
    first_ms: List[float] = []
    tokens = 0
    for r in requests:
        assert r.done, f"request {r.req_id} not done: {r.state}"
        tokens += len(r.tokens)
        if r.token_times:
            first_ms.append((r.token_times[0] - r.submit_time) * 1e3)
            gaps_ms.extend(np.diff(r.token_times) * 1e3)
    lat = {}
    if gaps_ms:
        lat = {"p50_token_ms": float(np.percentile(gaps_ms, 50)),
               "p99_token_ms": float(np.percentile(gaps_ms, 99))}
    return {
        "wall_s": wall_s,
        "tokens": tokens,
        "tokens_per_s": tokens / max(wall_s, 1e-9),
        "requests": len(requests),
        "evictions": sum(r.evictions for r in requests),
        "first_token_p50_ms": float(np.percentile(first_ms, 50))
        if first_ms else 0.0,
        **lat,
    }


def solo_reference(cfg, mesh, params, requests: Sequence[Request],
                   max_len: int, *, offload_kv: bool = False,
                   q_chunk: int = 256) -> Tuple[Dict[int, List[int]], float]:
    """Sequential solo decodes on the pre-capture jit path: each request
    prefilled and greedily decoded alone at batch 1.  Returns the
    per-request token sequences (the bit-parity oracle) and the timed
    sequential wall seconds (compiles excluded via warm-up)."""
    prefill, decode, make_cache = build_server(
        cfg, mesh, 1, max_len, q_chunk=q_chunk, offload_kv=offload_kv)

    def one(req: Request) -> List[int]:
        batch = batch_for_prompt(cfg, req.prompt)
        logits, cache = prefill(params, batch, make_cache())
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        if req.gen <= 1:
            return [int(np.asarray(tok)[0])]
        toks, _ = decode_stream(decode, params, tok, cache,
                                req.prompt_len, req.gen)
        return [int(np.asarray(t)[0]) for t in toks]

    # warm every (prompt-length, gen) executable pair off the clock: one
    # pass per distinct shape compiles prefill (per length) and decode
    # (once, on a prefill-output cache — a fresh init cache has different
    # sharding and would compile a second executable)
    seen = set()
    for req in requests:
        key = (req.prompt_len, req.gen > 1)
        if key not in seen:
            seen.add(key)
            one(req)

    t0 = time.perf_counter()
    out = {req.req_id: one(req) for req in requests}
    wall_s = time.perf_counter() - t0
    return out, wall_s


#: tolerance of every engine token against the teacher-forced solo logits,
#: in bf16 spacings at the reference's top logit (see
#: :func:`assert_logit_parity`)
LOGIT_TOL_ULPS = 8


def assert_logit_parity(cfg, mesh, params, requests: Sequence[Request],
                        oracle: Dict[int, List[int]], max_len: int, *,
                        offload_kv: bool = False,
                        q_chunk: int = 256) -> dict:
    """Check every engine token against the solo jit path's logits.

    The solo path is teacher-forced on each request's engine stream: it
    prefills the prompt, then decodes the engine's own tokens, so at every
    step both sides have the same history.  Every engine token must sit
    within ``LOGIT_TOL_ULPS`` bf16 spacings of the reference's top logit.
    Why a tolerance: the engine decodes a vmapped batch of slots and the
    reference one request at a time, so the same bf16 math accumulates in
    different orders, and an ulp of difference flips a near tie among a
    large vocabulary's bf16 logits (seen at full width on a TPU v5e;
    bitwise parity holds on the CPU backend, where the tests assert it
    with :func:`assert_parity`).  Returns the tokens checked, the number
    of requests whose stream left the free-running solo decode
    (``oracle``) at such a tie, and the largest gap, in bf16 spacings."""
    prefill, decode, make_cache = build_server(
        cfg, mesh, 1, max_len, q_chunk=q_chunk, offload_kv=offload_kv)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    stats = {"tokens": 0, "diverged": 0, "gap_max": 0.0}
    for r in requests:
        solo = oracle[r.req_id]
        if len(r.tokens) != len(solo):
            raise AssertionError(f"request {r.req_id}: {len(r.tokens)} "
                                 f"tokens, solo decode gave {len(solo)}")
        stats["diverged"] += list(r.tokens) != list(solo)
        logits, cache = prefill(params, batch_for_prompt(cfg, r.prompt),
                                make_cache())
        for i, tok in enumerate(r.tokens):
            if i:
                logits, cache = decode(
                    params, jnp.asarray([r.tokens[i - 1]], jnp.int32), cache,
                    jnp.int32(r.prompt_len + i - 1))
            lg = np.asarray(logits[0, -1], np.float32)
            top = float(lg.max())
            gap = (top - float(lg[tok])) / (eps * max(abs(top), 1e-30))
            stats["tokens"] += 1
            stats["gap_max"] = max(stats["gap_max"], gap)
            if gap > LOGIT_TOL_ULPS:
                raise AssertionError(
                    f"request {r.req_id}, token {i}: {tok} sits {gap:.1f} "
                    f"bf16 spacings below the teacher-forced solo path's "
                    f"top logit (tolerance {LOGIT_TOL_ULPS})")
    return stats


def assert_parity(requests: Sequence[Request],
                  oracle: Dict[int, List[int]]) -> None:
    """The bit-parity contract: every engine token sequence equals the
    solo jit decode of the same prompt, token for token."""
    for r in requests:
        np.testing.assert_array_equal(
            np.asarray(r.tokens), np.asarray(oracle[r.req_id]),
            err_msg=f"request {r.req_id} diverged from solo jit decode")
