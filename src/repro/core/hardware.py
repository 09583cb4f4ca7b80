"""Hardware catalog.

Reproduces the paper's Table 1 (eight Nvidia GPUs across five generations)
verbatim, extends the Nvidia lineage past the paper's Ampere endpoint with
the Hopper generation (figures from the vendor datasheets as quoted by the
Hopper microbenchmark papers, Luo et al. arXiv:2402.13499 / 2501.12084), and
adds the TPU generations this framework targets — the machine-balance
analysis (paper Fig. 1), the expected-speedup model (paper §6) and the
lineage validation (``repro.bench.lineage``) are computed over these records.

All numbers are peak/vendor figures, matching the paper's methodology
(techpowerup / vendor datasheets).  ``tdp_w`` / ``die_mm2`` may be 0.0 when
the vendor has not published them (recent TPUs); consumers must render such
sentinels as "n/a" — ``core.balance`` reports the derived densities as NaN.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class Chip:
    name: str
    vendor: str
    year: str
    arch: str
    grade: str                     # "datacenter" | "consumer" | "tpu"
    mem_gb: float
    mem_bw_gbs: float              # external memory bandwidth, GB/s
    tflops_f32: float              # fp32 (GPU) / bf16 (TPU — the lineage metric)
    tflops_f64: float              # 0.0 = no f64 units (TPUs)
    n_cores: int                   # SMs (GPU) / TensorCores-per-chip (TPU)
    tdp_w: float                   # 0.0 = unpublished (render as "n/a")
    die_mm2: float                 # 0.0 = unpublished (render as "n/a")
    # interconnect (per-link, unidirectional)
    link_gbs: float = 0.0
    vmem_mb: float = 0.0           # on-chip scratch (shared mem / VMEM)
    # async bulk-copy engine generation (lineage annotation): "" = plain
    # synchronous loads, "cp.async" = Ampere per-thread async copies,
    # "tma" = Hopper bulk tensor-memory accelerator, "dma" = TPU DMA engines
    async_engine: str = ""

    @property
    def has_f64(self) -> bool:
        """Whether the chip has native f64 units (TPUs do not)."""
        return self.tflops_f64 > 0.0

    @property
    def density_known(self) -> bool:
        """Whether die area is published (compute density is derivable)."""
        return self.die_mm2 > 0.0


# --- paper Table 1, verbatim -------------------------------------------------

GPUS: Tuple[Chip, ...] = (
    # Tesla / data-center
    Chip("K80", "nvidia", "2014Q4", "Kepler", "datacenter", 12, 240.6, 4.113, 1.371, 13, 300, 561),
    Chip("P100", "nvidia", "2016Q2", "Pascal", "datacenter", 16, 732.2, 10.61, 5.304, 56, 300, 610),
    Chip("V100", "nvidia", "2017Q3", "Volta", "datacenter", 16, 897.0, 14.13, 7.066, 80, 300, 815),
    Chip("A100", "nvidia", "2020Q3", "Ampere", "datacenter", 40, 1555.0, 19.49, 9.746, 108, 250, 826, async_engine="cp.async"),
    # Workstation / consumer
    Chip("GTX745", "nvidia", "2014Q1", "Maxwell", "consumer", 4, 28.80, 0.793, 0.02479, 3, 55, 148),
    Chip("K2200", "nvidia", "2014Q3", "Maxwell", "consumer", 4, 80.19, 1.439, 0.04496, 5, 68, 148),
    Chip("GTX1050Ti", "nvidia", "2016Q4", "Pascal", "consumer", 4, 112.1, 2.138, 0.0668, 6, 75, 132),
    Chip("RTX2060S", "nvidia", "2019Q3", "Turing", "consumer", 8, 448.0, 7.181, 0.224, 34, 175, 445),
)

# --- Hopper extension (past the paper) ---------------------------------------
# The paper stops at Ampere; these rows extend the datacenter lineage with the
# Hopper generation so the §6 expectation model becomes *predictive*.  Figures
# are vendor datasheet peaks (non-tensor f32/f64 vector throughput, matching
# the Table 1 convention) as quoted by the Hopper microbenchmark papers
# (Luo et al. arXiv:2402.13499, arXiv:2501.12084); the catalog-vs-published
# validation lives in experiments/baselines/LINEAGE_hopper.json +
# repro.bench.lineage.

HOPPER: Tuple[Chip, ...] = (
    Chip("H100-SXM", "nvidia", "2022Q4", "Hopper", "datacenter", 80, 3352.0, 66.91, 33.45, 132, 700, 814, async_engine="tma"),
    Chip("H100-PCIe", "nvidia", "2022Q4", "Hopper", "datacenter", 80, 2039.0, 51.22, 25.61, 114, 350, 814, async_engine="tma"),
    Chip("H200", "nvidia", "2024Q2", "Hopper", "datacenter", 141, 4890.0, 66.91, 33.45, 132, 700, 814, async_engine="tma"),
)

# --- TPU lineage extension ---------------------------------------------------
# tflops_f32 column holds bf16/matmul peak for TPUs (the throughput metric the
# lineage comparison uses); f64 is N/A on TPU (0.0).  TPUv5e/v5p tdp/die are
# unpublished -> 0.0 sentinels (consumers must print "n/a", never divide).

TPUS: Tuple[Chip, ...] = (
    Chip("TPUv2", "google", "2017", "TPUv2", "tpu", 8, 700.0, 45.0, 0.0, 2, 280, 0, link_gbs=62.5, vmem_mb=24, async_engine="dma"),
    Chip("TPUv3", "google", "2018", "TPUv3", "tpu", 16, 900.0, 123.0, 0.0, 2, 220, 0, link_gbs=81.25, vmem_mb=32, async_engine="dma"),
    Chip("TPUv4", "google", "2021", "TPUv4", "tpu", 32, 1200.0, 275.0, 0.0, 2, 170, 0, link_gbs=50.0, vmem_mb=128, async_engine="dma"),
    Chip("TPUv5e", "google", "2023", "TPUv5e", "tpu", 16, 819.0, 197.0, 0.0, 1, 0, 0, link_gbs=50.0, vmem_mb=128, async_engine="dma"),
    Chip("TPUv5p", "google", "2023", "TPUv5p", "tpu", 95, 2765.0, 459.0, 0.0, 2, 0, 0, link_gbs=100.0, vmem_mb=128, async_engine="dma"),
)

CATALOG: Dict[str, Chip] = {c.name: c for c in GPUS + HOPPER + TPUS}

#: the datacenter arc the lineage analysis walks (paper Table 1 order,
#: extended into Hopper).  H200 rides the same GH100 die at equal peak FLOPs
#: (only bandwidth moves), so it is validated as an A100/H100 pair in
#: ``repro.bench.lineage`` rather than a lineage step.
DATACENTER_LINEAGE: Tuple[str, ...] = (
    "K80", "P100", "V100", "A100", "H100-SXM")


# --- the framework's target chip ---------------------------------------------
# All roofline terms in launch/dryrun.py + benchmarks use these constants
# (given in the assignment): TPU v5e.

TARGET = CATALOG["TPUv5e"]
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per-chip per-direction)
VMEM_BYTES = 128 * 2 ** 20   # ~128 MiB VMEM per chip
HBM_BYTES = 16 * 2 ** 30     # 16 GiB per chip


def get_chip(name: str) -> Chip:
    try:
        return CATALOG[name]
    except KeyError:
        raise KeyError(f"unknown chip {name!r}; known: {sorted(CATALOG)}") from None


#: ``jax.devices()[0].device_kind`` -> catalog chip name, for the chips a
#: run can be attached to.  JAX calls v5e "TPU v5 lite" and v5p "TPU v5".
DEVICE_KINDS: Dict[str, str] = {
    "TPU v2": "TPUv2",
    "TPU v3": "TPUv3",
    "TPU v4": "TPUv4",
    "TPU v5 lite": "TPUv5e",
    "TPU v5": "TPUv5p",
}


def chip_for_device_kind(kind: str) -> Chip:
    """The catalog chip a device reports itself as.  An unknown kind is an
    error: a run is never labelled, or given peaks, of a chip it did not
    run on."""
    try:
        return CATALOG[DEVICE_KINDS[kind]]
    except KeyError:
        raise KeyError(f"unknown device_kind {kind!r}; known: "
                       f"{sorted(DEVICE_KINDS)}") from None
