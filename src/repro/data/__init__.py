from repro.data.images import synthetic_image, synthetic_batch, save_pgm

__all__ = [
    "synthetic_image",
    "synthetic_batch",
    "save_pgm",
]
