"""Propagation backend registry and device kernels.

`ops.py` is the front door: the `BackendSpec` registry behind
`run_propagation` (see docs/backends.md).  The kernel modules back the
registered backends — `propagate_pallas` (fused ELL), `bsr_spmv` (MXU
tiles), `landmark_propagate` (hot/cold approximate staging) — plus the
ingest argkmin pass and the Shiloach–Vishkin hook.  `platform.py`
decides where a kernel runs: compiled on a TPU, interpreted only where
no TPU exists (`interpret=None` everywhere).  Every backend also has an
exact XLA reference path (`ref`, the argkmin `xla` twin).
"""
