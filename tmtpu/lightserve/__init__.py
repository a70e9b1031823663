"""Light-client commit-proof serving tier.

One daemon process terminates many concurrent light-client sessions
against a single chain: it keeps its own verified spine (a LightStore
anchored at social-consensus TrustOptions), folds concurrent sync
requests for the same target height into ONE joint verification via a
height-keyed coalescer, and answers repeat queries from a bounded
trust-period-aware verified-height fact cache — so the Nth client
asking about a height costs zero device dispatches.

It is a framed daemon on the core it shares with :mod:`tmtpu.sidecar`
(:mod:`tmtpu.libs.framed`): ``python -m tmtpu.cmd lightserve`` speaks
length-prefixed frames, with an optional ``/healthz`` + ``/metrics``
listener.
"""

from tmtpu.lightserve.cache import Fact, VerifiedFactCache  # noqa: F401
from tmtpu.lightserve.client import (  # noqa: F401
    LightserveClient,
    LightserveError,
    LightserveOverloaded,
    LightserveRefused,
    LightserveUnavailable,
)
from tmtpu.lightserve.server import LightserveServer  # noqa: F401
