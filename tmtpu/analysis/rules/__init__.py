"""Rule plugins. Importing this package registers every rule.

Each module holds one rule (plus its constants); the registration side
effect happens at import, so ``registry.load_rules()`` importing this
package is the single activation point. Adding a rule = adding a module
here + one import line below (the meta rule then insists on its doc
entry, test coverage, and baseline status).
"""

from tmtpu.analysis.rules import (  # noqa: F401
    blocking_lock,
    determinism,
    exception_safety,
    failpoints,
    framed,
    jax_hygiene,
    lock_order,
    meta,
    metrics,
    obs_docs,
    recv_sync,
    scenarios,
    sigcache,
    timeline,
    wire_taint,
)
