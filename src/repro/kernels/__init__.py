# Pallas TPU kernels for the model zoo's compute hot-spots.
# Each kernel: <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
# oracle in ref.py, dispatching jit wrapper in ops.py; prefill_attention.py
# is called by models/attention.py directly.
from repro.kernels.ops import (decode_attention, flash_attention,
                               mlstm_chunk, ssm_scan)

__all__ = ["decode_attention", "flash_attention", "mlstm_chunk", "ssm_scan"]
