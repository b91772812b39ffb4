"""Optimizer substrate: AdamW with mesh-sharded states, cosine schedule,
global-norm clipping, and gradient compression for the DP axis."""
from repro.optim.adamw import (AdamWConfig, OptState, init_opt_state,
                               adamw_update, cosine_schedule,
                               clip_by_global_norm)
from repro.optim.compression import (compress_bf16, decompress_bf16,
                                     Int8State, compress_int8_ef,
                                     decompress_int8)
from repro.optim.reduce import (SCHEDULES, ReduceConfig, ReduceState,
                                backward_a2a_token, init_reduce_state,
                                n_chunks_for_bytes, reduce_gradients,
                                shard_n_chunks)
