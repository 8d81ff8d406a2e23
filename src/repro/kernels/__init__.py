"""Pallas TPU kernels (kernel.py + ops.py + ref.py each).

Importing this package registers every kernel's backends in
`repro.core.portable.registry` (the paper's portable-kernel catalogue).
"""

import repro.kernels.babelstream.ops  # noqa: F401
import repro.kernels.stencil7.ops  # noqa: F401
import repro.kernels.minibude.ops  # noqa: F401
import repro.kernels.hartree_fock.ops  # noqa: F401
import repro.kernels.flash_attention.ops  # noqa: F401
import repro.kernels.rwkv6.ops  # noqa: F401

# last (they import the ops modules above): attach the multi-device
# `xla_shard` backends + num_shards tunables, then the composite
# `shard_pallas` backends (shard_map around the Pallas kernels) with their
# tile x shard tunable spaces
import repro.distributed.domain  # noqa: F401
import repro.distributed.shard_pallas  # noqa: F401
