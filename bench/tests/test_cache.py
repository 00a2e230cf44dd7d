"""The window runs with JAX's persistent compilation cache off: a compile
there neither reads nor writes the cache directory, and
``plan.compiles_per_slice`` counts it."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import harness


def _listing(path):
    return sorted(p.name for p in path.iterdir()) if path.exists() else []


def _run(compiles, spans):
    return harness.Run(config={}, spans=spans, compiles=compiles,
                       answers=[], completed=0, window_s=1.0, launch={}, plans=[])


def test_window_compile_bypasses_the_cache(cache_dir):
    harness.configure_cache(cache_dir)
    rec = harness.Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_compile)
    try:
        x9, x11 = jnp.ones(9), jnp.ones(11)
        jax.jit(lambda x: jnp.sin(x) * 3.0)(x9).block_until_ready()
        before = _listing(cache_dir)
        assert before, "a compile with the cache on writes an entry"

        harness.use_persistent_cache(False)
        rec.on = True
        with rec.span("plan"):
            jax.jit(lambda x: jnp.cos(x) * 5.0)(x9).block_until_ready()
        with rec.span("segment"):
            jax.jit(lambda x: jnp.tan(x) * 2.0)(x11).block_until_ready()
        rec.on = False
        harness.use_persistent_cache(True)
    finally:
        jax.monitoring.unregister_event_duration_listener(rec.on_compile)

    assert _listing(cache_dir) == before
    read = harness.load_reader("plan.compiles_per_slice")
    assert read(_run(rec.compiles, rec.spans)) == 1.0  # the one inside the plan span


def test_compiles_per_slice_counts_by_span():
    read = harness.load_reader("plan.compiles_per_slice")
    spans = [("plan", 0.0, 1.0), ("segment", 0.0, 2.0), ("plan", 3.0, 4.0)]
    compiles = [(0.5, 0.1), (0.9, 0.1), (1.5, 0.2), (3.5, 0.3)]
    assert read(_run(compiles, spans)) == 1.5
    assert read(_run(compiles, [("segment", 0.0, 2.0)])) is None
