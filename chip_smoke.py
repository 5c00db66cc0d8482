"""Bring-up smoke: serve smollm-360m at its published widths on a TPU.

    python chip_smoke.py            # one chip: pallas vs ref, packed + chunked
    python chip_smoke.py --chips 4  # four chips: coplace_shmap vs default

One process drives the serving engine (``repro.serving.Engine``) the way
a user would: full-width ``smollm-360m`` (32 layers, d_model 960, 15
heads, 5 KV heads, vocab 49,152; random weights from ``--seed``), the
paper's H2EAL attention (select budget 4096, local 256, sink 4, pages of
32), a handful of greedy requests through two slots. It checks that

* every kernel is compiled by Mosaic: nothing runs interpreted, and the
  compiled prefill, prefill-chunk and decode steps contain
  ``tpu_custom_call``; every logit is finite;
* at full f32 matmul precision, the Pallas engine agrees with the
  pure-jnp reference engine, the chunked-prefill engine with the packed
  one (one chip), and the ``coplace_shmap`` layout on a (1, 4) ("data",
  "model") mesh with the default layout (``--chips 4``). Each pair must
  emit the same tokens, or diverge only where the reference's top-2
  logit margin is within the tolerance, and agree on every logit row up
  to the divergence.

It prints what it measured, then, as its last line, the JSON object
``{"ok": true, "device": {...}}``. It exits non-zero, with no such
line, when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "smollm-360m"
MAX_NEW = 16
# Prompt lengths: a 6k prompt is past smollm's 2,048-token published
# context, which is fine for a mechanism smoke with random weights. It
# is what makes top-k selection drop pages: below ~4.4k tokens
# (sink + select budget + local) every page is attended anyway.
PROMPTS = (1024, 2048, 1024, 6144)
CAPACITY = 6400               # 200 pages of 32, a multiple of 4 * 32
MAX_BATCH = 2
PREFILL_CHUNK = 512
# Agreement bound on logits, relative to the largest |logit| of the
# reference row, for engines run at full f32 matmul precision
# ("highest"), where they differ only in summation order; a wrong mask
# or page shows as an O(1) change. At JAX's default precision the TPU
# multiplies f32 in one bf16 pass: over 32 random-weight layers that
# alone moves logits by a few percent, so the default-precision run is
# checked for compiled kernels and finite logits, not compared.
REL_TOL = 1e-3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_secs: list = []      # every backend compile of this process


def _on_event(event, secs, **_):
    if event == COMPILE_EVENT:
        _compile_secs.append(secs)


def _requests(cfg, seed):
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=i, max_new=MAX_NEW,
                    prompt=rng.integers(0, cfg.vocab_size, size=(n,)
                                        ).astype(np.int32))
            for i, n in enumerate(PROMPTS)]


class _Compiled:
    """Wraps one of the engine's jitted steps: records the argument
    shapes of its first call, so the compiled program can be fetched
    again afterwards (from the persistent compile cache) and read."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            import jax
            # uncommitted arrays (a fresh prompt) follow the others
            self.args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None)
                if isinstance(x, jax.Array) else x, args)
        return self.fn(*args)

    def text(self, mesh, precision) -> str:
        import contextlib

        import jax
        # traced as the engine traced it: inside its mesh context, at
        # its matmul precision
        with jax.set_mesh(mesh) if mesh is not None else \
                contextlib.nullcontext(), \
                jax.default_matmul_precision(precision):
            return self.fn.lower(*self.args).compile().as_text()

    def _cache_size(self):       # Engine.jit_cache_sizes reads this
        return self.fn._cache_size()


def _instrument(eng):
    """Per-uid logits rows, in emission order, of every token the engine
    emits (the first token from prefill, then one row per decode step),
    plus wrappers around the compiled steps. Hooks the engine's private
    per-slot helpers: the engine keeps logits on the device."""
    import numpy as np

    rows, slot_uid = {}, {}
    set_sampling, first_token, sample = (
        eng._set_sampling, eng._first_token, eng._sample)

    def _set_sampling(req, slot):
        slot_uid[slot] = req.uid
        rows[req.uid] = []
        return set_sampling(req, slot)

    def _first_token(slot, logits_row):
        rows[slot_uid[slot]].append(np.asarray(logits_row, np.float32))
        return first_token(slot, logits_row)

    def _sample(logits, *rest):
        active = np.asarray(rest[-1])
        host = np.asarray(logits, np.float32)
        for slot in np.nonzero(active)[0]:
            rows[slot_uid[int(slot)]].append(host[slot])
        return sample(logits, *rest)

    _sample._cache_size = sample._cache_size   # for jit_cache_sizes()
    eng._set_sampling, eng._first_token, eng._sample = (
        _set_sampling, _first_token, _sample)
    steps = {}
    for name in ("_prefill", "_dec_sel", "_dec_reuse", "_chunk"):
        if hasattr(eng, name):
            steps[name] = _Compiled(getattr(eng, name))
            setattr(eng, name, steps[name])
    return rows, steps


def serve(cfg, params, requests, label, precision=None, **engine_kw):
    """Serve ``requests`` on a fresh engine, at ``precision`` for f32
    matmuls (None: JAX's default); returns a result dict with per-uid
    tokens and logits rows, and prints one summary line."""
    import jax
    import numpy as np

    from repro.serving import Engine

    n0 = len(_compile_secs)
    eng = Engine(cfg, params, max_batch=MAX_BATCH, capacity=CAPACITY,
                 prompt_buckets=sorted({len(r.prompt) for r in requests}),
                 **engine_kw)
    rows, steps = _instrument(eng)
    t0 = time.perf_counter()
    with jax.default_matmul_precision(precision):
        done = eng.run(requests)
    wall = time.perf_counter() - t0
    n_compiles = len(_compile_secs) - n0
    secs = sum(_compile_secs[n0:])
    tokens = {uid: c.tokens for uid, c in done.items()}
    devices = {d.id for leaf in jax.tree.leaves(eng.batch.serve)
               for d in leaf.sharding.device_set}
    for uid, toks in tokens.items():
        if len(toks) != MAX_NEW or len(rows[uid]) != MAX_NEW:
            raise AssertionError(f"{label}: uid {uid} emitted {len(toks)} "
                                 f"tokens, {len(rows[uid])} logit rows")
        if not all(np.isfinite(r).all() for r in rows[uid]):
            raise AssertionError(f"{label}: uid {uid} non-finite logits")
    print(f"[{label}] precision={precision or 'default'} "
          f"run_s={wall:.3f} (compile included) "
          f"compiles={n_compiles} compile_s={secs:.3f} "
          f"decode_steps={eng.stats.decode_steps} "
          f"tokens={sum(len(t) for t in tokens.values())} "
          f"state_devices={len(devices)} "
          f"jit_cache={eng.jit_cache_sizes()}", flush=True)
    return {"label": label, "tokens": tokens, "rows": rows,
            "steps": steps, "mesh": eng.mesh, "precision": precision,
            "devices": devices}


def check_kernels_compiled(res, names):
    """Every named step's compiled program contains a Mosaic kernel."""
    for name in names:
        step = res["steps"][name]
        if step.args is None:
            raise AssertionError(f"[{res['label']}] {name} never ran")
        if "tpu_custom_call" not in step.text(res["mesh"],
                                              res["precision"]):
            raise AssertionError(
                f"[{res['label']}] compiled {name} has no tpu_custom_call")
    print(f"[{res['label']}] tpu_custom_call in {', '.join(names)}",
          flush=True)


def compare(res, ref):
    """``res`` agrees with the reference run ``ref``: per request, logits
    rows within REL_TOL up to the first differing token, and a token
    difference only where the reference's top-2 margin is a near tie
    (within 2 * REL_TOL)."""
    import numpy as np

    worst, ties = 0.0, 0
    for uid, want in ref["tokens"].items():
        got = res["tokens"][uid]
        n = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                 len(want))
        errs = []
        for i in range(min(n + 1, len(want))):
            r, g = ref["rows"][uid][i], res["rows"][uid][i]
            err = float(np.abs(g - r).max() / np.abs(r).max())
            errs.append(err)
            worst = max(worst, err)
            if not np.isfinite(err) or err > REL_TOL:
                raise AssertionError(
                    f"{res['label']} vs {ref['label']}: uid {uid} token "
                    f"{i}: max |dlogit| / max |logit| = {err:.3g}")
        print(f"  uid {uid}: equal tokens {n}/{len(want)}, "
              f"max |logit| {np.abs(ref['rows'][uid][0]).max():.3f}, "
              f"rel logit diff first {errs[0]:.2e} max {max(errs):.2e}",
              flush=True)
        if n < len(want):
            r = ref["rows"][uid][n]
            top2 = np.sort(r)[-2:]
            margin = float((top2[1] - top2[0]) / np.abs(r).max())
            if margin > 2 * REL_TOL:
                raise AssertionError(
                    f"{res['label']} vs {ref['label']}: uid {uid} diverges "
                    f"at token {n} with top-2 margin {margin:.3g}")
            ties += 1
    print(f"[{res['label']} vs {ref['label']}] tokens_equal="
          f"{ties == 0} near_tie_divergences={ties} "
          f"max_rel_logit_diff={worst:.3e} (bound {REL_TOL})", flush=True)


def one_chip(cfg, params, requests):
    # the path as deployed, at JAX's default matmul precision
    pal = serve(cfg, params, requests, "pallas", impl="pallas")
    check_kernels_compiled(pal, ["_prefill", "_dec_sel", "_dec_reuse"])
    # the same requests at full f32 precision: pallas against the jnp
    # reference, chunked prefill against packed
    pal32 = serve(cfg, params, requests, "pallas_f32", "highest",
                  impl="pallas")
    ref32 = serve(cfg, params, requests, "ref_f32", "highest", impl="ref")
    compare(pal32, ref32)
    del ref32
    chunked = serve(cfg, params, requests, "pallas_chunked_f32", "highest",
                    impl="pallas", prefill_chunk=PREFILL_CHUNK)
    check_kernels_compiled(chunked, ["_chunk", "_dec_sel", "_dec_reuse"])
    compare(chunked, pal32)


def four_chips(cfg, params, requests):
    import jax
    from jax.sharding import AxisType

    if len(jax.devices()) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, "
                             f"found {len(jax.devices())}")
    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    base = serve(cfg, params, requests, "default_f32", "highest",
                 impl="pallas")
    shmap = serve(cfg, params, requests, "coplace_shmap_f32", "highest",
                  impl="pallas", layout="coplace_shmap", mesh=mesh)
    check_kernels_compiled(shmap, ["_prefill", "_dec_sel", "_dec_reuse"])
    if len(shmap["devices"]) != 4:
        raise AssertionError(
            f"coplace_shmap state on devices {sorted(shmap['devices'])}")
    compare(shmap, base)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.configs import get_arch
    from repro.core.paging import page_counts
    from repro.kernels import ops
    from repro.models import model as M
    from repro.runtime.compile_cache import enable_compile_cache

    if ops.interpret_mode():
        raise AssertionError("Pallas kernels would run interpreted")
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    cfg = get_arch(ARCH)
    h2 = cfg.h2eal
    long_ctx = max(PROMPTS) + MAX_NEW
    n_pages = -(-long_ctx // h2.page_size)
    n_sink, n_local = page_counts(sink=h2.sink, local=h2.local,
                                  page=h2.page_size)
    attended = n_sink + h2.top_k_pages + n_local
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    print(f"model {ARCH}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} vocab={cfg.vocab_size}"
          f"; prompts={PROMPTS} max_new={MAX_NEW}; longest request "
          f"{n_pages} pages, {attended} attended per decode step",
          flush=True)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    requests = _requests(cfg, args.seed)
    if args.chips == 4:
        four_chips(cfg, params, requests)
    else:
        one_chip(cfg, params, requests)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
