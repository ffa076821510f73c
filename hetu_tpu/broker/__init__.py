"""Elastic chip market: leases between training and serving.

One chip inventory, two workloads — :class:`CapacityBroker` moves
capacity between the :class:`~hetu_tpu.exec.gang.ElasticGang` and the
serving fleet as journaled, seeded-replayable leases, following the
diurnal traffic shape (grant at sustained SLO burn, reclaim LIFO when
pressure releases).  See ``broker.py`` for the loop,
``lease.py`` for the record/state machine, and ``episode.py`` for the
deterministic end-to-end episode driver the acceptance tests run.
"""

from hetu_tpu.broker.broker import (BrokerConfig, CapacityBroker,
                                    broker_families, get_broker, install,
                                    use)
from hetu_tpu.broker.lease import LEASE_STATES, Lease, LeaseStateError

__all__ = ["BrokerConfig", "CapacityBroker", "broker_families",
           "install", "get_broker", "use",
           "Lease", "LeaseStateError", "LEASE_STATES"]
