# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

# Kernels: ecc_decode, ecc_encode, ecc_qmatmul (fused decode+matmul),
# flash_attention, quant_throttle, throttle. Wrappers in ops.py; oracles in
# ref.py. platform.py decides the mode once: compiled on a TPU backend,
# interpreted on CPU (where the test suite validates them).
