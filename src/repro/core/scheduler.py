"""Analytic performance / resource models.

Implements the paper's Sec. 3.5 model exactly (Eqs. 1-4) so the evaluation
tables can be reproduced and validated, then re-derives the same style of
model for the TPU v5e target (the hardware-adaptation required by this port).

Paper model (FPGA, H_A sparse-matrix HBM channels, 512-bit Rd/Wr):
    #BRAMs     = 32 · H_A                                   (Eq. 1)
    #URAMs     = 8 · H_A · U                                (Eq. 2)
    row depth  = 16 · H_A · U · D                           (Eq. 3)
    #cycles    = (M + K)/16 + NNZ/(8 · H_A)                 (Eq. 4)

The TPU re-derivation keeps the paper's structure — a streaming term plus an
on-chip processing term — but with TPU constants:
    t_stream = (8·slots + 4·(K_pad + 2·M_pad)) / BW_hbm
    t_gather = tiles · cycles_per_tile / f_vpu
    t        = max(t_stream, t_gather)        (perfect overlap: the Pallas
               pipeline double-buffers chunk DMA against VPU processing, the
               analogue of the paper's Rd-module / PE decoupling FIFOs)
"""
from __future__ import annotations

import dataclasses


# --------------------------------------------------------------------------
# FPGA model (the paper, verbatim)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FPGASpec:
    freq_hz: float = 223e6          # Serpens v16 (Table 1)
    sparse_channels: int = 16       # H_A
    vector_lanes: int = 16          # 512-bit / 32-bit fp32

    @property
    def pes(self) -> int:
        return 8 * self.sparse_channels


SERPENS_V16 = FPGASpec()
SERPENS_V24 = FPGASpec(freq_hz=270e6, sparse_channels=24)


def fpga_brams(spec: FPGASpec) -> int:
    return 32 * spec.sparse_channels                       # Eq. 1


def fpga_urams(spec: FPGASpec, urams_per_pe: int = 3) -> int:
    return 8 * spec.sparse_channels * urams_per_pe         # Eq. 2


def fpga_row_depth(spec: FPGASpec, urams_per_pe: int = 3,
                   uram_depth: int = 4096) -> int:
    return 16 * spec.sparse_channels * urams_per_pe * uram_depth   # Eq. 3


def fpga_cycles(m: int, k: int, nnz: int, spec: FPGASpec = SERPENS_V16,
                padded_slots: int | None = None) -> float:
    """Paper Eq. 4.  ``padded_slots`` (if given) replaces NNZ with the actual
    stream length incl. null padding — the measured-vs-ideal gap in Table 3 is
    exactly this padding/imbalance factor."""
    work = nnz if padded_slots is None else padded_slots
    return (m + k) / spec.vector_lanes + work / spec.pes


def fpga_time_s(m, k, nnz, spec: FPGASpec = SERPENS_V16, padded_slots=None):
    return fpga_cycles(m, k, nnz, spec, padded_slots) / spec.freq_hz


def mteps(nnz: int, time_s: float) -> float:
    """Million traversed edges per second — the paper's throughput metric."""
    return nnz / time_s / 1e6


# --------------------------------------------------------------------------
# Device peaks: the one table, keyed by jax's ``device_kind``
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    source: str


DEVICE_PEAKS = {
    # jax reports a TPU v5e chip as "TPU v5 lite".
    "TPU v5 lite": DevicePeaks(
        hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
        source='Google Cloud TPU documentation, "TPU v5e"'),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Published peaks of one chip; a device not in the table is an error
    (a roofline share against a guessed peak is no measurement)."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None


# --------------------------------------------------------------------------
# TPU v5e model (the hardware adaptation)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TPUSpec:
    device_kind: str = "TPU v5 lite"
    ici_bw: float = 50e9            # bytes/s per link
    vpu_freq_hz: float = 940e6
    lanes: int = 128
    sublanes: int = 8
    vmem_bytes: int = 64 * 2**20    # budgeted working VMEM
    # Cycles for one (8,128) tile: decode + gather + fma + scatter.  The
    # baseline (unoptimized) kernel issues gather and scatter element-serial
    # per sublane: 8 gather + 8 scatter + ~2 overhead.
    cycles_per_tile_baseline: float = 18.0
    # Hillclimbed kernel (see EXPERIMENTS.md §Perf): conflict-free tiles let
    # scatter retire one full tile per issue window.
    cycles_per_tile_optimized: float = 10.0

    @property
    def hbm_bw(self) -> float:
        """HBM bytes/s per chip, from :data:`DEVICE_PEAKS`."""
        return device_peaks(self.device_kind).hbm_bytes_per_s


TPU_V5E = TPUSpec()


def tpu_stream_bytes(m: int, k: int, slots: int, read_y_in: bool = True):
    """One full SpMV pass: A stream + x once + y write (+ y read if β≠0)."""
    y_bytes = 4 * m * (2 if read_y_in else 1)
    return 8 * slots + 4 * k + y_bytes


def tpu_spmv_time(m: int, k: int, nnz: int, slots: int,
                  spec: TPUSpec = TPU_V5E, optimized: bool = False):
    """Returns (time_s, dict of term breakdown)."""
    tiles = slots / (spec.lanes * spec.sublanes)
    cpt = (spec.cycles_per_tile_optimized if optimized
           else spec.cycles_per_tile_baseline)
    t_stream = tpu_stream_bytes(m, k, slots) / spec.hbm_bw
    t_gather = tiles * cpt / spec.vpu_freq_hz
    t = max(t_stream, t_gather)
    return t, {
        "t_stream_s": t_stream,
        "t_gather_s": t_gather,
        "bound": "memory" if t_stream >= t_gather else "gather",
        "mteps": mteps(nnz, t),
        "bw_frac": t_stream / t,   # fraction of roofline (stream = roofline)
    }


# --------------------------------------------------------------------------
# Paper evaluation data (Tables 2, 3, 5) for validation
# --------------------------------------------------------------------------
# id: (name, vertices, nnz, serpens_ms, serpens_mteps, graphlily_mteps,
#      serpens_v24_mteps)
PAPER_TABLE3 = {
    "G1": ("googleplus", 108_000, 13_700_000, 1.87, 7_300, 7_920, 7_606),
    "G2": ("crankseg_2", 63_800, 14_100_000, 0.930, 15_214, 9_639, 17_943),
    "G3": ("Si41Ge41H72", 186_000, 15_000_000, 0.853, 17_594, 8_117, 22_262),
    "G4": ("TSOPF_RS_b2383", 38_100, 16_200_000, 0.730, 22_144, 10_296,
           30_204),
    "G5": ("ML_Laplace", 377_000, 27_600_000, 1.37, 20_099, 9_305, 25_796),
    "G6": ("mouse_gene", 45_100, 29_000_000, 1.37, 21_098, 10_331, 28_937),
    "G7": ("soc_pokec", 1_630_000, 30_600_000, 4.52, 6_782, 4_352, 8_708),
    "G8": ("coPapersCiteseer", 434_000, 21_100_000, 2.09, 15_324, 8_828,
           17_990),
    "G9": ("PFlow_742", 743_000, 37_100_000, 2.05, 18_142, 8_212, 22_969),
    "G10": ("ogbl_ppa", 576_000, 42_500_000, 2.04, 20_847, 9_243, 27_680),
    "G11": ("hollywood", 1_070_000, 113_000_000, 6.20, 18_176, 9_094, 22_330),
    "G12": ("ogbn_products", 2_450_000, 124_000_000, 6.32, 19_565, 6_668,
            25_278),
}

PAPER_GEOMEAN_MTEPS = 15_876        # Serpens v16, Table 3
PAPER_GEOMEAN_SPEEDUP_GRAPHLILY = 1.91
PAPER_MAX_MTEPS_V24 = 30_204        # Table 5
