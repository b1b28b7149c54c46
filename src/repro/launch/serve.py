"""Serving driver: batched prefill + decode with xMem cache budgeting.

Before allocating KV caches, the xMem serving estimator sizes the peak
so the server picks the largest batch that fits — the serving analogue
of the training admission gate. The gate covers BOTH serving phases:
the prefill peak (full-prompt forward with the cache resident) and the
decode-step peak. Gating on the decode step alone — the original bug —
admits batches that OOM during prefill, before a single token decodes.

Two gates live here (ISSUE 9):

* ``pick_batch`` — the static gate: largest fixed batch whose
  monolithic-cache prefill/decode estimates fit;
* ``pick_serving`` — the request-driven gate: a continuous-batching
  runtime over a ``RequestMix`` (paged KV cache, prefix sharing,
  speculative scratch) gated on the worst-case peak of the scripted
  timeline, with serving counter-offers (page size / concurrency /
  KV dtype) on rejection.

Estimates route through the admission service
(:mod:`repro.service.admission`), so repeated gate decisions are warm
(content-addressed trace cache) and, with ``--store-dir``, survive
restarts.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
      --max-len 64 --tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
      --serve-mix 48:16:8,16:48:8 --max-concurrent 8 --page-size 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke
from ..models import model as M
from ..train.train_step import make_prefill_step


def decode_input(cfg, b: int, abstract: bool = True):
    """One-token decode batch for ``M.decode_step``."""
    if abstract:
        tok = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)  # noqa: E731
    else:
        tok = lambda *sh: jnp.zeros(sh, jnp.int32)             # noqa: E731
    if cfg.family == "audio":
        return {"codes": tok(b, 1, cfg.num_codebooks)}
    return {"tokens": tok(b, 1)}


def prompt_specs(cfg, b: int, seq: int) -> dict:
    """Full-prompt prefill batch (no labels — serving, not training)."""
    tok = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)  # noqa: E731
    if cfg.family == "vlm":
        P = cfg.num_patches
        return {"patch_embeds": jax.ShapeDtypeStruct((b, P, cfg.d_model),
                                                     cfg.dtype),
                "tokens": tok(b, max(seq - P, 8))}
    if cfg.family == "audio":
        return {"codes": tok(b, seq, cfg.num_codebooks)}
    return {"tokens": tok(b, seq)}


def make_decode_fn(cfg):
    def decode(params, cache, batch):
        return M.decode_step(params, cache, batch, jnp.int32(0), cfg)
    return decode


def make_prefill_fn(cfg):
    """(params, cache, batch) prefill wrapper: the KV cache rides along
    as persistent state so the prefill estimate includes it."""
    step = make_prefill_step(cfg)

    def prefill(params, cache, batch):
        return step(params, batch), cache
    return prefill


def serving_cache_profile(cfg, max_len: int,
                          probe_delta: int = 8) -> tuple[int, int]:
    """(kv_bytes_per_token, resident_bytes_per_request) of ``cfg``'s
    decode cache — the continuous-batching scheduler's byte inputs.

    Classified by finite differencing ``init_cache`` totals at two max
    lengths (batch 1): the slope is the paged, length-proportional KV
    footprint per token; the intercept is the per-request resident
    state that never pages (SSM / conv state in the ssm and hybrid
    families — constant-size, so a paged server must keep it whole per
    active slot)."""
    def total(L):
        tree = jax.eval_shape(lambda: M.init_cache(cfg, 1, L))
        out = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            n = 1
            for dim in leaf.shape:
                n *= int(dim)
            out += n * leaf.dtype.itemsize
        return out
    lo, hi = total(max_len), total(max_len + probe_delta)
    kv_tok = max((hi - lo) // probe_delta, 0)
    resident = max(lo - kv_tok * max_len, 0)
    return int(kv_tok), int(resident)


def _gate_service(service, store_dir):
    """The admission service a gate call runs against. ``store_dir``
    threads the CLI's persistent trace store through to library callers
    — previously a ``service=None`` call silently rebuilt a storeless
    service and every gate decision re-traced after a restart."""
    if service is not None:
        return service
    from ..service import AdmissionService
    return AdmissionService(workers=1, store_dir=store_dir)


def pick_batch(cfg, max_len: int, hbm_bytes: int,
               candidates=(64, 32, 16, 8, 4, 2, 1), service=None,
               store_dir=None):
    """Largest batch whose serving estimates fit (binary-search-free).

    Gates on ``max(prefill, decode)`` peak. Returns ``(batch, gate)``
    where ``gate`` holds the admitting prefill/decode decisions, or
    ``(None, gate)`` — an explicit no-fit result — when no candidate
    fits (including an empty candidate list or estimates that raise).
    Every failing candidate records its own error in
    ``gate["errors"]`` (``{batch, error}`` rows, in trial order);
    ``gate["error"]`` keeps the most recent one for compact
    reporting."""
    svc = _gate_service(service, store_dir)
    params = M.abstract_params(cfg)
    decode_fn = make_decode_fn(cfg)
    prefill_fn = make_prefill_fn(cfg)
    gate: dict = {"candidates": [], "errors": [], "error": None}
    for b in candidates:
        cache = jax.eval_shape(lambda: M.init_cache(cfg, b, max_len))
        try:
            dec = svc.decide_serving(
                f"{cfg.name}-b{b}-decode", decode_fn, params, cache,
                decode_input(cfg, b), capacity=hbm_bytes)
            pre = svc.decide_serving(
                f"{cfg.name}-b{b}-prefill", prefill_fn, params, cache,
                prompt_specs(cfg, b, max_len), capacity=hbm_bytes)
        except Exception as e:  # noqa: BLE001 — record, try a smaller batch
            err = f"{type(e).__name__}: {e}"
            gate["errors"].append({"batch": b, "error": err})
            gate["error"] = err
            continue
        peak = max(pre.peak_bytes, dec.peak_bytes)
        gate["candidates"].append(
            {"batch": b, "prefill_peak": pre.peak_bytes,
             "decode_peak": dec.peak_bytes, "peak": peak,
             "fits": peak <= hbm_bytes})
        if peak <= hbm_bytes:
            gate.update(batch=b, prefill=pre, decode=dec, peak=peak)
            return b, gate
    return None, gate


def pick_serving(cfg, mix, hbm_bytes: int, *, knobs=None, space=None,
                 max_len: int | None = None, service=None,
                 store_dir=None):
    """Request-driven serving gate: admit/reject a request mix under a
    continuous-batching runtime, with serving counter-offers on
    rejection.

    Returns ``(decision, gate)``. ``gate["serving"]`` carries the
    :class:`~repro.core.estimator.ServingEstimate` summary (worst-case
    vs steady-state peak, paged-vs-monolithic cache bytes);
    ``decision.counter_offers`` is populated when ``space`` enables
    serving axes and the mix does not fit. The decode step is traced at
    batch 1 — every knob candidate (and every ``pick_serving`` retry)
    shares that one cached trace."""
    from ..core.orchestrator import ServingKnobs
    svc = _gate_service(service, store_dir)
    knobs = knobs or ServingKnobs()
    stream = mix.stream() if hasattr(mix, "stream") else mix
    if max_len is None:
        max_len = max(stream.max_seq_len, 8)
    kv_tok, resident = serving_cache_profile(cfg, max_len)
    params = M.abstract_params(cfg)
    decode_fn = make_decode_fn(cfg)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 1, max_len))
    plan = None
    if space is not None:
        from ..plan import ServingPlanContext
        plan = ServingPlanContext(
            decode_fn, params, cache, decode_input(cfg, 1), mix,
            knobs=knobs, kv_bytes_per_token=kv_tok,
            resident_bytes_per_request=resident, space=space)
    decision = svc.decide_serving(
        f"{cfg.name}-mix", decode_fn, params, cache,
        decode_input(cfg, 1), capacity=hbm_bytes, mix=mix, knobs=knobs,
        kv_bytes_per_token=kv_tok, resident_bytes_per_request=resident,
        plan=plan)
    gate = {"serving": decision.breakdown.get("serving", {}),
            "kv_bytes_per_token": kv_tok,
            "resident_bytes_per_request": resident,
            "max_len": max_len}
    return decision, gate


def parse_mix(spec: str, arrival_period: int = 1,
              shared_prefix_len: int = 0):
    """``prompt:decode:count[,prompt:decode:count...]`` -> RequestMix."""
    from ..core.orchestrator import RequestMix
    buckets = []
    for part in spec.split(","):
        p, d, c = (int(x) for x in part.split(":"))
        buckets.append((p, d, c))
    return RequestMix(buckets=tuple(buckets),
                      arrival_period=max(int(arrival_period), 1),
                      shared_prefix_len=max(int(shared_prefix_len), 0))


def serve_mix_main(cfg, args, svc) -> int:
    """``--serve-mix`` entry: request-driven gate + offer printout."""
    from ..core.orchestrator import ServingKnobs
    from ..plan import PlanSpace
    mix = parse_mix(args.serve_mix, args.arrival_period,
                    args.shared_prefix)
    knobs = ServingKnobs(page_size=args.page_size,
                         max_concurrent=args.max_concurrent,
                         kv_dtype_bytes=args.kv_dtype_bytes,
                         prefix_cache=not args.no_prefix_cache,
                         speculative_k=args.speculative_k)
    space = None
    if args.plan:
        space = PlanSpace(
            page_sizes=(8, 16, 32),
            max_concurrents=tuple(sorted({max(args.max_concurrent // 2, 1),
                                          args.max_concurrent,
                                          args.max_concurrent * 2})),
            kv_dtypes=(1, 2))
    decision, gate = pick_serving(cfg, mix, int(args.hbm_gib * 2**30),
                                  knobs=knobs, space=space,
                                  max_len=args.max_len, service=svc)
    s = gate["serving"]
    verdict = "admitted" if decision.admit else "rejected"
    print(f"[xmem] serve-mix {cfg.name}: {verdict} — worst-case "
          f"{s.get('worst_case_peak_bytes', decision.peak_bytes)/2**20:.1f}"
          f" MiB / steady "
          f"{s.get('steady_state_peak_bytes', 0)/2**20:.1f} MiB vs "
          f"{args.hbm_gib:.2f} GiB "
          f"(paged {s.get('paged_kv_peak_bytes', 0)/2**20:.1f} MiB vs "
          f"monolithic {s.get('monolithic_cache_bytes', 0)/2**20:.1f} "
          f"MiB; source {decision.provenance['source']})")
    for i, o in enumerate(decision.counter_offers or ()):
        k = o.serving["knobs"]
        print(f"[xmem]   offer #{i+1}: page={k['page_size']} "
              f"c={k['max_concurrent']} kv{8*k['kv_dtype_bytes']} "
              f"prefix={'on' if k['prefix_cache'] else 'off'} "
              f"peak={o.peak_bytes/2**20:.1f} MiB "
              f"slowdown=x{o.slowdown:.2f}")
    return 0 if decision.admit else 2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--hbm-gib", type=float, default=16.0)
    ap.add_argument("--store-dir", default=None,
                    help="persistent trace store for the serving gate")
    ap.add_argument("--serve-mix", default=None,
                    help="request-driven gate: prompt:decode:count[,...]")
    ap.add_argument("--arrival-period", type=int, default=1)
    ap.add_argument("--shared-prefix", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-concurrent", type=int, default=8)
    ap.add_argument("--kv-dtype-bytes", type=int, default=2)
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--speculative-k", type=int, default=0)
    ap.add_argument("--plan", action="store_true",
                    help="on rejection, search serving counter-offers")
    args = ap.parse_args()

    from .device import enable_compile_cache
    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    from ..service import AdmissionService
    svc = AdmissionService(workers=1, store_dir=args.store_dir)
    if args.serve_mix:
        return serve_mix_main(cfg, args, svc)
    batch, gate = pick_batch(cfg, args.max_len,
                             int(args.hbm_gib * 2**30), service=svc)
    if batch is None:
        err = f" ({gate['error']})" if gate.get("error") else ""
        print(f"[xmem] no serving batch fits "
              f"{args.hbm_gib:.2f} GiB{err} -> rejected")
        return 2
    print(f"[xmem] serving batch={batch} "
          f"(peak {gate['peak']/2**20:.1f} MiB = max(prefill "
          f"{gate['prefill'].peak_bytes/2**20:.1f}, decode "
          f"{gate['decode'].peak_bytes/2**20:.1f}); "
          f"gate source {gate['decode'].provenance['source']})")

    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch, args.max_len)
    if cfg.family == "audio":
        tok = jnp.zeros((batch, 1, cfg.num_codebooks), jnp.int32)
        batch_fn = lambda t: {"codes": t}          # noqa: E731
    else:
        tok = jnp.zeros((batch, 1), jnp.int32)
        batch_fn = lambda t: {"tokens": t}         # noqa: E731

    @jax.jit
    def step(params, cache, tok, pos):
        return M.decode_step(params, cache, batch_fn(tok), pos, cfg)

    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, cache = step(params, cache, tok, jnp.int32(i))
        nxt = jnp.argmax(logits[..., -1, :] if cfg.family != "audio"
                         else logits[:, -1], axis=-1).astype(jnp.int32)
        tok = nxt.reshape(tok.shape)
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens x batch {batch} in {dt:.2f}s "
          f"({args.tokens * batch / dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
