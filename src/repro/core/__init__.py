"""Core library: the paper's contribution.

Early-stopping list intersection for depth-first frequent itemset mining
(Eclat / dEclat / PrePost+), as published, plus the TPU-native bitmap
engine and the count-distribution distributed miner.
"""

from repro.core.oracle import (  # noqa: F401
    MiningStats, mine, mine_bruteforce, mine_eclat, mine_declat,
    mine_prepost, PPCTree, item_frequencies, frequent_items_ascending,
)
from repro.core.bitmap import (  # noqa: F401
    BitmapDB, pack_tidlists, unpack_row, popcount32, popcount32_np,
    suffix_popcounts, suffix_popcounts_np, DEFAULT_BLOCK_WORDS,
)
from repro.core.rowstore import DeviceRowStore, NListPool  # noqa: F401
from repro.core.frontier import (  # noqa: F401
    ClassNode, EngineAccounting, FrontierScheduler,
)
from repro.core.eclat import (  # noqa: F401
    BitmapMiner, DeviceMiningStats, mine_bitmap,
)
from repro.core.prepost import DevicePrePost, mine_prepost_device  # noqa: F401
from repro.core.distributed import (  # noqa: F401
    DistributedMiner, DistributedStats,
)
