"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``, the
fullest of the cell's devices), read when the window closes."""

from benchmark import loader

read = loader.load_sibling(__file__, "_shared").hbm_peak_gb
