"""device.peak_hbm_pct: the fullest chip's peak memory over its HBM.

``peak_bytes_in_use`` of ``device.memory_stats()`` after the window, the
largest over the chips used, over the HBM bytes of ``bench/peaks.json``.
"""


def read(rec):
    if not rec.memory_peak_bytes:
        return None
    return 100.0 * rec.memory_peak_bytes / rec.peaks["hbm_bytes"]
