"""Pallas TPU kernels for the perf-critical compute layers.

<name>.py holds the pl.pallas_call + BlockSpec kernel; ops.py the jitted
wrappers (interpret mode on CPU, compiled on TPU); ref.py the pure-jnp
oracles every kernel is validated against.

Kernels:
* flash_attention — blocked online-softmax GQA attention (train/prefill)
* decode_attention — flash-decode vs long (possibly ring) KV caches
* ssd_scan — full chunked Mamba2/SSD with in-VMEM recurrent state
* ssm_update — a decode's one-token SSD step, in place in the stacked states
* quantize — blockwise int8 for the compressed gradient collective
* digest — blockwise lattice digest for accelerator-placed integrity
"""
