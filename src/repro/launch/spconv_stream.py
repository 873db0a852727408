"""Streaming inference driver: a moving-sensor replay through MinkUNet.

The end-to-end face of the DESIGN.md §15 delta path (the streaming
sibling of ``--arch minkunet`` training in launch/train.py): one
long-lived :class:`~repro.core.stream.StreamSession` holds a pinned
stage-1 QueryTable per resolution level, and every frame of a
:func:`~repro.data.pointcloud.moving_sensor_sequence` is diffed against
it — only the dirty neighborhoods are re-searched, untouched kmap rows
are reused verbatim, and an unchanged frame costs zero searches. The
per-frame report prints which path each level took (delta / full /
content hit), the searched-row count, and the forward wall clock:

    PYTHONPATH=src python -m repro.launch.spconv_stream \
        --frames 12 --voxels 1024 --window 192 --step 4

``--no-stream`` replays the same sequence with the delta path disabled
(every frame rebuilt from scratch) for an A/B on the same machine;
``benchmarks/stream_replay.py`` runs both and gates their parity and
search ratio in CI.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.core import stream
from repro.data.pointcloud import moving_sensor_sequence
from repro.models import minkunet
from repro.runtime import feature_cache

CONFIGS = {
    "tiny": minkunet.MinkUNetConfig(name="stream-tiny", in_ch=3, classes=4,
                                    stem=8, enc=(8, 8), dec=(8, 8),
                                    blocks=1, grid_bits=5, batch_bits=2),
    "small": minkunet.MinkUNetConfig(name="stream-small", in_ch=3,
                                     classes=8, stem=16, enc=(16, 32),
                                     dec=(32, 16), blocks=1, grid_bits=6,
                                     batch_bits=2),
}


def run_stream(cfg, n_frames: int, n: int, *, max_blocks: int | None = None,
               window: int = 192, step: int = 4, depth: int = 16,
               density: float = 0.15, seed: int = 0,
               enabled: bool | None = None, impl: str | None = None,
               pinned_bytes: int | None = None,
               log=print) -> dict:
    """Replay ``n_frames`` through one long-lived session; returns the
    session stats plus wall-clock aggregates. ``log=None`` silences the
    per-frame report (library use)."""
    store = feature_cache.PinnedStore(pinned_bytes) if pinned_bytes \
        else feature_cache.default_store()
    sess = stream.StreamSession(
        cfg, n, max_blocks=max_blocks, search_impl=impl, enabled=enabled,
        cache=planlib.PlanCache(pinned=store))
    params = minkunet.init_model(cfg, jax.random.key(seed))
    frames = moving_sensor_sequence(np.random.default_rng(seed), n_frames,
                                    n, window=window, step=step,
                                    depth=depth, density=density)
    advance_ms, forward_ms = [], []
    for t, f in enumerate(frames):
        before = sess.stats()
        t0 = time.perf_counter()
        delta = sess.advance(f.coords, f.batch, f.valid)
        jax.block_until_ready(sess.states[0].kmap)
        t1 = time.perf_counter()
        logits = sess.forward(params, jnp.asarray(f.feats[:, :cfg.in_ch]))
        jax.block_until_ready(logits)
        t2 = time.perf_counter()
        advance_ms.append((t1 - t0) * 1e3)
        forward_ms.append((t2 - t1) * 1e3)
        if log is not None:
            inc = {k: v - before[k] for k, v in sess.stats().items()}
            log(f"frame {t:3d}: valid={int(f.valid.sum()):5d} "
                f"dirty={int(delta.n_dirty_rows):5d} "
                f"levels(delta/full/hit)={inc['delta_levels']}/"
                f"{inc['full_levels']}/{inc['content_hit_levels']} "
                f"searched={inc['rows_searched']:5d}"
                f"/{inc['rows_scratch']:5d} "
                f"plan={t1 - t0:6.3f}s fwd={t2 - t1:6.3f}s")
    stats = sess.stats()
    sess.close()
    out = {
        **stats,
        "advance_ms_mean": float(np.mean(advance_ms)),
        "forward_ms_mean": float(np.mean(forward_ms)),
        "search_fraction":
            stats["rows_searched"] / max(stats["rows_scratch"], 1),
        "reused_kmap_row_fraction":
            stats["kmap_rows_reused"] / max(stats["kmap_rows_total"], 1),
        "pinned": store.stats(),
    }
    if log is not None:
        log(f"-- {stats['frames']} frames: searched "
            f"{out['search_fraction']:.1%} of the from-scratch rows, "
            f"reused {out['reused_kmap_row_fraction']:.1%} of kmap rows, "
            f"advance {out['advance_ms_mean']:.1f} ms/frame "
            f"(forward {out['forward_ms_mean']:.1f} ms)")
        log(f"   pinned store: {out['pinned']}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", choices=sorted(CONFIGS), default="tiny")
    ap.add_argument("--frames", type=int, default=12)
    ap.add_argument("--voxels", type=int, default=1024)
    ap.add_argument("--max-blocks", type=int, default=None)
    ap.add_argument("--window", type=int, default=192)
    ap.add_argument("--step", type=int, default=4)
    ap.add_argument("--depth", type=int, default=16)
    ap.add_argument("--density", type=float, default=0.15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default=None,
                    help="OCTENT search impl (pallas|interpret|ref)")
    ap.add_argument("--no-stream", action="store_true",
                    help="disable the delta path (from-scratch baseline)")
    ap.add_argument("--pinned-bytes", type=int, default=None,
                    help="private PinnedStore byte budget (default: the "
                         "process-wide store)")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    run_stream(CONFIGS[args.config], args.frames, args.voxels,
               max_blocks=args.max_blocks, window=args.window,
               step=args.step, depth=args.depth, density=args.density,
               seed=args.seed, impl=args.impl,
               enabled=False if args.no_stream else None,
               pinned_bytes=args.pinned_bytes)


if __name__ == "__main__":
    main()
