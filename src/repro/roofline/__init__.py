from .analysis import HW, PEAKS, RooflineCell, analyze_cell, format_table, load_cells, model_flops, peaks
from .hlo_parse import HLOAnalysis, analyze_hlo

__all__ = [
    "HW",
    "PEAKS",
    "peaks",
    "RooflineCell",
    "analyze_cell",
    "format_table",
    "load_cells",
    "model_flops",
    "HLOAnalysis",
    "analyze_hlo",
]
