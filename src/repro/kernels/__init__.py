# Pallas kernels.  ring_matmul.py (the fused NoP ring collectives) and
# flash_attention.py (the attention core of a train step, on one chip or on
# each chip's heads of a mesh) are on the model path; matmul.py and ssd.py are standalone kernels checked against
# ref.py by tests/test_kernels.py.
