# Pallas kernels.  ring_matmul.py (the fused NoP ring collectives) is on the
# model path; matmul.py, flash_attention.py and ssd.py are standalone kernels
# checked against ref.py by tests/test_kernels.py.
