"""paddle_tpu.ops.pallas — the Pallas kernel tier (ISSUE 13).

A small registry/dispatch layer (:mod:`.registry`) plus the kernels
profiles said XLA fusion leaves speed on the table for.  Every kernel
ships with its XLA-reference implementation as BOTH the fallback path
and the parity oracle, runs under the Pallas interpreter on CPU (so
tier-1 pins parity without hardware), and exposes python-side dispatch
counters proving which path ran (mirrored to ``/metrics`` as
``pallas_dispatch{kernel=,path=}``).

Kernels (see each module's docstring for the tolerance contract):

- ``flash_attention`` — blockwise attention (PR 2-era kernel, now
  registry-governed; ``ops/flash_attention`` stays as a compat path)
- ``opt_apply`` — fused sgd/momentum/adam over a flat ZeRO shard
- ``int8_matmul`` — int8-weight matmul with in-tile dequant (serving)
- ``int8_kv_attention`` — paged decode/verify attention reading int8
  KV pools once, per-(block, slot) scales applied inside the gather
- ``paged_attention`` — decode attention over bf16 paged KV pools read
  through the block table up to each row's length (no gather into HBM)
- ``segment_sum`` — device-side fused sparse-grad merge mirroring
  ``native/ps_core.cc``'s ``ps_segsum_inv``
- ``pull_dequant`` — on-device reconstruction of int8 PS pull rows
  (the tiered PS q8 wire's egress saving carried through the
  host->device copy)

Escape hatch: ``PADDLE_PALLAS=0`` routes everything to the XLA
references; ``PADDLE_PALLAS_<KERNEL>=pallas|xla_ref|interpret``
overrides one kernel.
"""
from . import registry  # noqa: F401
from .flash_attention import (flash_attention,  # noqa: F401
                              flash_attention_bhsd)
from .int8_matmul import int8_matmul_pallas, int8_matmul_ref  # noqa: F401
from .kv_attention import (int8_paged_attention,  # noqa: F401
                           paged_attention, paged_attention_ref)
from .opt_apply import opt_apply_pallas, opt_apply_ref  # noqa: F401
from .pull_dequant import (pull_dequant_pallas,  # noqa: F401
                           pull_dequant_ref)
from .registry import (dispatch, dispatch_counts, kernels,  # noqa: F401
                       reset_dispatch_counts, resolve, set_mode)
from .segment_sum import segment_sum_pallas, segment_sum_ref  # noqa: F401
