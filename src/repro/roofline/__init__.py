from .analysis import (DRYRUN_DEVICE_KIND, PEAKS, collective_bytes_from_hlo,
                       peaks, roofline_terms, summarize_cell)

__all__ = ["DRYRUN_DEVICE_KIND", "PEAKS", "collective_bytes_from_hlo",
           "peaks", "roofline_terms", "summarize_cell"]
