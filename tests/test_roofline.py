"""Roofline analyzer tests: the trip-scaled HLO walker against programs
with known FLOP counts (XLA's own cost_analysis counts loop bodies once —
the motivation for the walker; see roofline/analysis.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import hw
from repro.roofline.analysis import (
    RooflineTerms,
    analyze_hlo,
    call_multipliers,
    parse_computations,
    top_contributors,
)


def compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


class TestWalker:
    def test_plain_matmul_flops(self):
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        hlo = compile_text(lambda x, y: x @ y, a, b)
        c = analyze_hlo(hlo)
        assert c.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.05)

    def test_scan_trip_scaling(self):
        """The critical property: loop bodies scale by trip count."""
        def f(x, w):
            def body(c, wi):
                return c @ wi, ()
            y, _ = jax.lax.scan(body, x, w)
            return y

        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((24, 128, 128), jnp.float32)
        hlo = compile_text(f, x, w)
        c = analyze_hlo(hlo)
        assert c.flops == pytest.approx(2 * 24 * 128**3, rel=0.02)

    def test_nested_scan_trip_scaling(self):
        def f(x, w):
            def inner(c, wi):
                return jnp.tanh(c @ wi), ()

            def outer(c, wc):
                y, _ = jax.lax.scan(inner, c, wc)
                return y, ()

            y, _ = jax.lax.scan(outer, x, w.reshape(3, 8, 64, 64))
            return y

        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((24, 64, 64), jnp.float32)
        c = analyze_hlo(compile_text(f, x, w))
        assert c.flops == pytest.approx(2 * 24 * 64**3, rel=0.05)

    def test_bytes_positive_and_bounded(self):
        a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        hlo = compile_text(lambda x: x + 1.0, a)
        c = analyze_hlo(hlo)
        nbytes = 256 * 256 * 4
        assert nbytes <= c.bytes <= 4 * nbytes

    def test_empty_hlo(self):
        c = analyze_hlo("")
        assert c.flops == 0.0


class TestPublicApi:
    """The promoted HLO-walking API (parse_computations /
    call_multipliers / top_contributors) that scripts/hlo_top.py and
    analyze_hlo share."""

    def _scan_hlo(self):
        def f(x, w):
            def body(c, wi):
                return c @ wi, ()
            y, _ = jax.lax.scan(body, x, w)
            return y

        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((24, 128, 128), jnp.float32)
        return compile_text(f, x, w)

    def test_parse_computations_entry(self):
        comps = parse_computations(self._scan_hlo())
        assert "__entry__" in comps
        entry = comps["__entry__"]
        assert comps[entry.name] is entry
        assert entry.ops  # ENTRY has instructions

    def test_call_multipliers_trip_scaled(self):
        """The while body's multiplier carries the trip count."""
        comps = parse_computations(self._scan_hlo())
        mult, fused = call_multipliers(comps)
        assert mult[comps["__entry__"].name] == 1.0
        assert max(mult.values()) >= 24.0  # loop body runs 24x
        assert set(fused) == set(mult)

    def test_call_multipliers_empty(self):
        assert call_multipliers({}) == ({}, {})

    def test_top_contributors_agree_with_analyze_hlo(self):
        """Drill-down FLOPs sum to the roofline total (shared multiplier
        propagation — the point of the refactor)."""
        hlo = self._scan_hlo()
        dots = sum(v for v, _, _ in top_contributors(hlo, "flops"))
        assert dots == pytest.approx(2 * 24 * 128**3, rel=0.01)
        total_bytes = sum(v for v, _, _ in top_contributors(hlo, "bytes"))
        assert total_bytes == pytest.approx(analyze_hlo(hlo).bytes, rel=1e-9)

    def test_top_contributors_sorted_and_limited(self):
        hlo = self._scan_hlo()
        contrib = top_contributors(hlo, "bytes")
        assert contrib == sorted(contrib, key=lambda t: -t[0])
        assert top_contributors(hlo, "bytes", limit=2) == contrib[:2]

    def test_top_contributors_bad_mode(self):
        with pytest.raises(ValueError):
            top_contributors("", "nope")


class TestTerms:
    def test_dominant_selection(self):
        t = RooflineTerms(flops=1e15, hbm_bytes=1e12, collective_bytes=1e13, chips=256,
                          device_kind=hw.V5E)
        assert t.compute_s > 0
        assert t.dominant == "collective"
        assert t.step_time_s == t.collective_s

    def test_scaling_invariance(self):
        """Per-chip time terms are independent of the chip count used to
        scale totals (totals = per-device x chips)."""
        t1 = RooflineTerms(flops=256e12, hbm_bytes=256e9, collective_bytes=0, chips=256,
                           device_kind=hw.V5E)
        t2 = RooflineTerms(flops=512e12, hbm_bytes=512e9, collective_bytes=0, chips=512,
                           device_kind=hw.V5E)
        assert t1.compute_s == pytest.approx(t2.compute_s)
        assert t1.memory_s == pytest.approx(t2.memory_s)

    def test_peaks_keyed_by_device_kind(self):
        v5e = hw.peaks("TPU v5 lite")
        assert v5e.flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
        t = RooflineTerms(flops=197e12, hbm_bytes=0, collective_bytes=0, chips=1,
                          device_kind=hw.V5E)
        assert t.compute_s == pytest.approx(1.0)
        with pytest.raises(KeyError, match="no roofline peaks"):
            hw.peaks("TPU v9 imaginary")
        bad = RooflineTerms(flops=1, hbm_bytes=1, collective_bytes=0, chips=1,
                            device_kind="cpu")
        with pytest.raises(KeyError):
            bad.compute_s
        with pytest.raises(TypeError, match="device_kind"):
            RooflineTerms(flops=1, hbm_bytes=1, collective_bytes=0, chips=1)
