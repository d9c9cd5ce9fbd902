"""Rule registry: ``ALL_RULES`` maps rule name → check function.

A check function takes a :class:`~tools.graftcheck.core.Project` and
yields :class:`~tools.graftcheck.core.Finding` objects.  Adding a rule =
adding a module here and one entry below (see
docs/how_to/static_analysis.md "Adding a rule").
"""

from .atomic_write import check_atomic_write
from .envvars import check_env_var_registry
from .chaos_sites import check_chaos_sites
from .metrics_discipline import check_metrics_hot_path
from .typed_errors import check_typed_errors
from .lock_discipline import check_lock_discipline
from .jit_purity import check_jit_purity
from .golden_metrics import check_golden_metrics

ALL_RULES = {
    "env-var-registry": check_env_var_registry,
    "chaos-site": check_chaos_sites,
    "metrics-hot-path": check_metrics_hot_path,
    "typed-errors": check_typed_errors,
    "lock-discipline": check_lock_discipline,
    "jit-purity": check_jit_purity,
    "golden-metrics": check_golden_metrics,
    "atomic-write": check_atomic_write,
}

__all__ = ["ALL_RULES"]
