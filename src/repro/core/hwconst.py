"""Phi accelerator hardware constants — the single source of truth.

Every number that describes the modelled hardware lives here, imported by
both perf stories the repo carries:

  * the first-order analytical model (``core.perfmodel``) — closed-form
    cycle/energy/traffic expressions;
  * the cycle-approximate event-driven simulator (``repro.sim``) — the
    same parameters driving discrete per-stripe events.

Keeping them in one module is what lets ``tests/test_sim.py`` cross-check
the two against each other: a drifting copy would silently decouple the
stories the CI gate compares.

Architecture parameters (paper Table 1 / Sec. 4, 28nm @ 500 MHz) and the
Table 2/3 power figures are annotated inline; the per-access energies are
28nm-class ballparks (synthesis-report orders of magnitude, not measured)
chosen so that integrated core energy at full utilisation is consistent
with the Table 3 core power — the simulator's energy claims are *ratios*
against a baseline modelled with the same constants.
"""
from __future__ import annotations

# ------------------------------------------------------------------ clock ---
FREQ = 500e6                    # Hz (Table 1)

# ------------------------------------------------------------------- DRAM ---
DRAM_GBPS = 64e9                # DDR4, Table 1: 64 GB/s
DRAM_BPC = DRAM_GBPS / FREQ     # bytes per core cycle (= 128 B/cycle)
DRAM_PJ_PER_BYTE = 20.0         # pJ per byte (DRAMsim-class DDR4 ballpark)
DRAM_STATIC_W = 0.5             # DDR4 4-channel background power

# ------------------------------------------------------------- core power ---
CORE_POWER_W = 0.3466           # Phi total incl. buffers (Table 3)
EYERISS_POWER_W = 0.56          # area-scaled from Table 2 (1.068 vs 0.662 mm²)

# ------------------------------------------------------ Phi microarch dims ---
MATCHER_WIDTH = 16              # row-tiles matched per cycle (matcher array)
CHANNELS = 8                    # L1/L2 adder-tree channels
SIMD = 32                       # vector lanes per channel
ARRAY_UTIL = 0.7                # adder-tree pipeline/sync/skipping efficiency
PE_EYERISS = 168                # Eyeriss PE count (paper baseline config)
PWP_BUFFER_KB = 128             # on-chip PWP buffer (prefetcher working set)
PACKER_CAP = 4096               # L2 packer entry capacity per M-stripe round
PACKER_RATE = 16                # L2 entries packed per cycle

# -------------------------------------------------- per-access energy (pJ) ---
# 28nm-class dynamic energies per primitive event. The simulator charges
# exactly these (its energy total is, by construction, the sum over unit
# ledgers — asserted in tests/test_sim.py), so the constants are the whole
# dynamic-energy story.
E_MATCH_PJ = 2.0                # one q-way Hamming match of a k-wide row tile
E_SIMD_OP_PJ = 1.2              # one 32-lane adder-tree accumulate
E_PACK_PJ = 0.3                 # one L2 entry through the packer
E_SRAM_RD_PJ_B = 0.05           # on-chip buffer read, per byte
E_SRAM_WR_PJ_B = 0.08           # on-chip buffer write, per byte
E_MAC_PJ = 2.3                  # one baseline 8-bit PE MAC (Eyeriss-class)

# ------------------------------------------------- TPU kernel-path launch ---
# One Pallas kernel dispatch, expressed in HBM byte-equivalents at the
# Table-1 bandwidth (~1 µs of launch/teardown at 64 GB/s). Used by the
# execution policy's cost crossover (see perfmodel.phi_coo_traffic).
PALLAS_LAUNCH_BYTES = 64 * 1024

# --------------------------------------------------------- TPU (serving) ----
# The TPU-side constants the jax_pallas serving path is modelled against.
# Kept here with the ASIC constants for the same reason: the execution
# policy's VMEM gate, the roofline report and the bench baselines must all
# read one copy (PHI-LINT-HWCONST enforces it).
# Scoped VMEM the fused Phi kernels ask Mosaic for (v5e's default scoped
# limit is 16 MiB of a 128 MiB core), and the part of it the execution
# policy's byte models may fill with double-buffered, (8, 128)-tile-padded
# blocks; the rest is left for the kernel body's temporaries.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024
VMEM_BUDGET_BYTES = 32 * 1024 * 1024
TPU_PEAK_FLOPS = 197e12         # bf16 per chip (TPU v5e)
TPU_HBM_BW = 819e9              # bytes/s per chip
TPU_ICI_BW = 50e9               # bytes/s per link
