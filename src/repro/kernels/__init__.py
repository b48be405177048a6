# Pallas TPU kernels for the serving hot spots (DESIGN.md §8):
#   attention.py — one online-softmax GQA body behind prefill (flash),
#                  chunked prefill and decode, over contiguous or paged KV
#   ssd_scan.py  — Mamba2 SSD chunked scan
# ops.py — jit'd dispatch (interpret=True on CPU); ref.py — pure-jnp oracles.
