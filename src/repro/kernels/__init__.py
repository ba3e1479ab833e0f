"""Pallas TPU kernels for AccurateML's compute hot spots.

The paper's map-task hot loops (distance scans for kNN, Pearson-weight scans
for CF, and the stage-1/stage-2 attention analogue) dominate >95 % of job
computation time (paper Fig. 4), so they get explicit MXU/VMEM tilings here.

Layout per kernel:
  <name>.py — pl.pallas_call + BlockSpec VMEM tiling (TPU target)
  ref.py    — pure-jnp oracles shared by all kernels
  ops.py    — jit'd dispatch wrappers (TPU: pallas, CPU: ref;
              tests: pallas interpret mode vs ref; REPRO_FORCE_KERNELS
              pins the default path process-wide)

The fused two-stage hot path (`distance_topk`, `topk_stream`,
`refine_distances`, `cf_refine`) replaces materialize-then-reduce with
stream-and-carry: a per-query running k-best lives in VMEM scratch across
grid steps and refinement rows are DMA'd from HBM one by one, so the
[Q,N] distance matrix and [Q,B,D]/[Q,B,I] gathered tensors never exist.
"""
