"""The framed-daemon core: one socket protocol shape, written once.

The verification sidecar (:mod:`tmtpu.sidecar`) and the light-client
serving tier (:mod:`tmtpu.lightserve`) are each a daemon that speaks
length-prefixed typed frames over a unix or tcp socket, gathers requests
from many connections into joint work, and answers each caller its own
share. That shape lives here — ``protocol.py`` (frame, codec, shared
codes and messages), ``client.py``, ``server.py`` and ``gather.py`` (the
keyed gather queue) — and knows neither daemon: each passes in its
namespace module, its metric objects and its name prefix as values, and
keeps only its own messages, engine and policy.
"""
