from .ops import (dtw_batched, dtw_batched_pairs, dtw_distances,
                  dtw_distances_pairs)
from .ref import dtw_matrix_ref
from .score import (score_bank_offline, score_bank_offline_kernel,
                    score_bank_offline_var_approx_kernel,
                    score_bank_offline_var_kernel)
from .stream import stream_bank_extend_kernel, stream_bank_extend_scored_kernel

__all__ = ["dtw_batched", "dtw_batched_pairs", "dtw_distances",
           "dtw_distances_pairs", "dtw_matrix_ref",
           "score_bank_offline", "score_bank_offline_kernel",
           "score_bank_offline_var_kernel",
           "score_bank_offline_var_approx_kernel",
           "stream_bank_extend_kernel", "stream_bank_extend_scored_kernel"]
