from .ops import (brsgd_partials, brsgd_select_combine, brsgd_stats,
                  cwise_median, fused_stats, masked_mean, trimmed_mean)
from . import ref
