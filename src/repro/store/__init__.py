"""MGit storage: CAS dedup, codecs, delta compression, versioned checkpoints."""

from repro.store.artifact_store import ArtifactStore
from repro.store.cas import CAS
from repro.store.checkpoint import (CheckpointManager, flatten_state,
                                    unflatten_state)
from repro.store.codecs import CODECS, get_codec
from repro.store.delta import (CompressResult, ParamDelta, decompress_param,
                               lcs_param_matching)

__all__ = [
    "ArtifactStore", "CAS", "CheckpointManager", "flatten_state",
    "unflatten_state", "CODECS", "get_codec", "CompressResult", "ParamDelta",
    "decompress_param", "lcs_param_matching",
]
