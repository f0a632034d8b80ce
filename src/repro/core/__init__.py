"""Federated Forest core — the paper's contribution as a composable JAX module."""
from repro.core.boosting import BoostParams, FederatedBoosting  # noqa: F401
from repro.core.fedlinear import FederatedLinear, LinearParams  # noqa: F401
from repro.core.forest import FederatedForest, fit_federated_forest  # noqa: F401
from repro.core.party import (VerticalPartition, make_vertical_partition,  # noqa: F401
                              partition_from_blocks)
from repro.core.partyblock import (CSVSource, DataSource, PartyBlock,  # noqa: F401
                                   align_party_blocks)
from repro.core.types import ForestParams, PARTY_AXIS  # noqa: F401
from repro.observability import profiler as _profiler

_profiler.install()      # program spans reach any jax.profiler session
