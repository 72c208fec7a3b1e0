"""Analytic model vs the paper's published numbers (reproduction check)."""
import math

import numpy as np
import pytest

from repro.core import scheduler as S


def geomean(xs):
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


class TestPaperModel:
    def test_eq1_eq2_eq3(self):
        spec = S.SERPENS_V16
        assert S.fpga_brams(spec) == 512                 # 32·16
        assert S.fpga_urams(spec, urams_per_pe=3) == 384  # Table 4 URAM
        assert S.fpga_row_depth(spec, 3, 4096) == 16 * 16 * 3 * 4096

    def test_eq4_cycle_model_bounds_measurements(self):
        """Eq.4 is an ideal lower bound: modeled time ≤ measured time for
        every Table-3 matrix, and within 3× (padding/imbalance overhead)."""
        for gid, (name, verts, nnz, ms, *_rest) in S.PAPER_TABLE3.items():
            t_model = S.fpga_time_s(verts, verts, nnz) * 1e3
            assert t_model <= ms * 1.02, (gid, t_model, ms)
            assert t_model >= ms / 3.5, (gid, t_model, ms)

    def test_geomean_throughput_reproduction(self):
        """Modeled geomean MTEPS is within 2× of the paper's 15,876 and the
        per-matrix measured values average ≥55% of the ideal model."""
        model = [S.mteps(nnz, S.fpga_time_s(v, v, nnz))
                 for (_, v, nnz, *_r) in S.PAPER_TABLE3.values()]
        reported = [r[4] for r in S.PAPER_TABLE3.values()]
        gm_model, gm_rep = geomean(model), geomean(reported)
        assert gm_rep == pytest.approx(S.PAPER_GEOMEAN_MTEPS, rel=0.02)
        assert 1.0 <= gm_model / gm_rep <= 2.0
        effs = [r / m for r, m in zip(reported, model)]
        assert geomean(effs) > 0.55

    def test_v24_scaling_direction(self):
        """24 channels + 270 MHz must model faster than v16 on every
        matrix, matching Table 5's uniform improvement."""
        for gid, (name, v, nnz, *_r) in S.PAPER_TABLE3.items():
            t16 = S.fpga_time_s(v, v, nnz, S.SERPENS_V16)
            t24 = S.fpga_time_s(v, v, nnz, S.SERPENS_V24)
            assert t24 < t16

    def test_v24_max_throughput_claim(self):
        """Paper: max 30,204 MTEPS on G4 — the model admits it (ideal model
        ≥ measured)."""
        _, v, nnz, *_r = S.PAPER_TABLE3["G4"]
        assert S.mteps(nnz, S.fpga_time_s(v, v, nnz, S.SERPENS_V24)) \
            >= S.PAPER_MAX_MTEPS_V24


class TestTPUModel:
    def test_spmv_is_memory_bound(self):
        t, terms = S.tpu_spmv_time(1_000_000, 1_000_000, 30_000_000,
                                   slots=33_000_000)
        assert terms["bound"] in ("memory", "gather")
        # AI = 0.25 flops/byte → far below the 240 flops/byte ridge
        ai = 2 * 30e6 / S.tpu_stream_bytes(1_000_000, 1_000_000, 33_000_000)
        assert ai < 1.0

    def test_optimized_kernel_not_slower(self):
        a = S.tpu_spmv_time(10_000, 10_000, 1_000_000, 1_100_000,
                            optimized=False)[0]
        b = S.tpu_spmv_time(10_000, 10_000, 1_000_000, 1_100_000,
                            optimized=True)[0]
        assert b <= a

    def test_padding_increases_time(self):
        base = S.tpu_spmv_time(10_000, 10_000, 1_000_000, 1_000_000)[0]
        padded = S.tpu_spmv_time(10_000, 10_000, 1_000_000, 2_000_000)[0]
        assert padded > base


class TestDevicePeaks:
    def test_v5e_row_is_the_published_peak(self):
        p = S.device_peaks("TPU v5 lite")
        assert p.hbm_bytes_per_s == 819e9
        assert p.bf16_flops_per_s == 197e12
        assert "TPU v5e" in p.source

    def test_unknown_device_is_an_error(self):
        for kind in ("cpu", "TPU v4", ""):
            with pytest.raises(KeyError, match="no peaks"):
                S.device_peaks(kind)

    def test_tpu_model_reads_the_table(self):
        assert S.TPU_V5E.hbm_bw == S.DEVICE_PEAKS["TPU v5 lite"].hbm_bytes_per_s
