"""Self-registering lint passes, in execution order.

Registration order is severity-logical: def-use first (everything else
assumes a well-formed graph), the abstract interpreter second (later
passes may consult its findings), then the graph-hygiene and hazard
passes.
"""
from . import defuse  # noqa: F401
from . import shapes  # noqa: F401
from . import liveness  # noqa: F401
from . import aliasing  # noqa: F401
from . import retrace  # noqa: F401
from . import numeric  # noqa: F401
from . import emit_coverage  # noqa: F401
from . import sharding  # noqa: F401
from . import memplan  # noqa: F401
from . import donation  # noqa: F401
