"""Peak device memory of the run, read after the window, on the fullest
chip, in GiB: the peak of its buffers (``peak_bytes_in_use``) plus the peak
the TPU runtime reserved for the programs' temporaries
(``peak_bytes_reserved``), where the dense-slab staging lives."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return {"value": run.memory_peak_bytes / 2**30}
