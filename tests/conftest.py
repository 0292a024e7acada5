"""Shared test plumbing.

Puts ``tools/`` on the path and — under ``SEACHECK=1`` — arms the
seacheck runtime lock-order detector *before* any test module imports
``repro`` (dataclass ``default_factory=threading.Lock`` binds the
factory at class-creation time, so the patch must win that race).
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOOLS = os.path.join(os.path.dirname(_HERE), "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

if os.environ.get("SEACHECK") == "1":
    from seacheck import runtime as _seacheck_runtime

    _seacheck_runtime.install()
    # adopt the plugin's per-test drain fixture + session-end sweep
    from seacheck.pytest_plugin import (  # noqa: F401
        _seacheck_findings_guard,
        pytest_sessionfinish,
    )
