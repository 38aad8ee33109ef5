"""Spark-style TPU resource discovery and device assignment.

Parity target: the reference's deployment contract
(``/root/reference/README.md:81-89``) — ``spark.task.resource.gpu.amount``,
``spark.executor.resource.gpu.amount`` and a ``discoveryScript``
(``getGpusResources.sh``) that prints Spark's ResourceInformation JSON, plus
the per-task device resolution ``gpuId == -1 ⇒
TaskContext.resources()("gpu").addresses(0)``
(``RapidsRowMatrix.scala:171-175``). Here the resource name is ``tpu``, the
discovery script ships as package data (``discovery_script_path()``), and
assignment
resolves to a JAX device ordinal. Discovery never initializes the JAX
backend unless explicitly asked (backend init can block on a hung device
backend — see utils/health.py).
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional

RESOURCE_NAME = "tpu"

# Spark conf keys, with "gpu" swapped for "tpu" (SURVEY.md §5 config table).
TASK_AMOUNT_KEY = "spark.task.resource.tpu.amount"
EXECUTOR_AMOUNT_KEY = "spark.executor.resource.tpu.amount"
DISCOVERY_SCRIPT_KEY = "spark.executor.resource.tpu.discoveryScript"

_ENV_VISIBLE = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES")
_ENV_TASK_DEVICE = "SPARK_RAPIDS_ML_TPU_DEVICE"


@dataclass
class ResourceInformation:
    """Mirror of ``org.apache.spark.resource.ResourceInformation`` — the
    JSON shape a discovery script must print."""

    name: str
    addresses: List[str] = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"name": self.name, "addresses": self.addresses})

    @staticmethod
    def from_json(text: str) -> "ResourceInformation":
        obj = json.loads(text)
        if not isinstance(obj.get("name"), str) or not isinstance(
            obj.get("addresses"), list
        ):
            raise ValueError(f"not a ResourceInformation payload: {text!r}")
        return ResourceInformation(
            name=obj["name"], addresses=[str(a) for a in obj["addresses"]]
        )


class ResourceConf:
    """Two-level config resolution, mirroring the reference's Spark-conf +
    Params split (§5): a properties mapping (``spark.*`` keys) consulted by
    the runtime, with typed accessors for the tpu resource keys.
    """

    def __init__(self, conf: Optional[Mapping[str, str]] = None):
        self._conf: Dict[str, str] = dict(conf or {})

    @staticmethod
    def from_properties(text: str) -> "ResourceConf":
        """Parse ``key value`` / ``key=value`` lines (spark-defaults.conf
        syntax: comments with #, blank lines ignored)."""
        conf: Dict[str, str] = {}
        for raw in text.splitlines():
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            # split at the FIRST separator so values containing '=' (java
            # options, paths) survive intact
            m = re.match(r"^([^=\s]+)\s*[=\s]\s*(.*)$", line)
            if m:
                conf[m.group(1)] = m.group(2).strip()
        return ResourceConf(conf)

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._conf.get(key, default)

    def task_tpu_amount(self, default: float = 0.0) -> float:
        return float(self.get(TASK_AMOUNT_KEY, str(default)))

    def executor_tpu_amount(self, default: int = 0) -> int:
        return int(float(self.get(EXECUTOR_AMOUNT_KEY, str(default))))

    def discovery_script(self) -> Optional[str]:
        return self.get(DISCOVERY_SCRIPT_KEY)


def discovery_script_path() -> str:
    """Absolute path of the packaged discovery script — what to set
    ``spark.executor.resource.tpu.discoveryScript`` to. Ships as package
    data so installed (non-checkout) deployments have it."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "get_tpus_resources.sh",
    )


def discover_tpu_addresses(probe_jax: bool = False) -> List[str]:
    """Enumerate local TPU chip addresses, cheapest signal first:

    1. ``TPU_VISIBLE_CHIPS``/``TPU_VISIBLE_DEVICES`` env (explicit pinning);
    2. ``/dev/accel*`` device nodes (how TPU VMs expose chips);
    3. optionally (``probe_jax=True``) ``jax.local_devices()`` — accurate
       but initializes the backend, which can block when the device
       backend hangs.
    """
    for var in _ENV_VISIBLE:
        val = os.environ.get(var)
        if val:
            return [a.strip() for a in val.split(",") if a.strip()]
    # numeric sort (matching the shell script's `sort -n`): lexicographic
    # order would interleave accel10 between accel1 and accel2
    nodes = sorted(
        glob.glob("/dev/accel[0-9]*"),
        key=lambda n: int(re.sub(r"^/dev/accel", "", n)),
    )
    if nodes:
        return [re.sub(r"^/dev/accel", "", n) for n in nodes]
    if probe_jax:
        import jax

        # filter by platform: on a TPU-less host local_devices() falls back
        # to CPU devices, which must not be advertised as tpu addresses
        return [str(d.id) for d in jax.local_devices() if d.platform == "tpu"]
    return []


def discovery_json(probe_jax: bool = False) -> str:
    """What the discovery script prints — the exact contract
    ``spark.executor.resource.tpu.discoveryScript`` expects."""
    return ResourceInformation(
        RESOURCE_NAME, discover_tpu_addresses(probe_jax=probe_jax)
    ).to_json()


def resolve_device_ordinal(
    device_id: int = -1,
    task_resources: Optional[Mapping[str, ResourceInformation]] = None,
    env: Optional[Mapping[str, str]] = None,
) -> int:
    """Which local device a task should use.

    Precedence mirrors ``RapidsRowMatrix.scala:171-175``: an explicit
    ``deviceId != -1`` wins; otherwise the task's assigned resource
    addresses (the TaskContext analogue); otherwise the
    ``SPARK_RAPIDS_ML_TPU_DEVICE`` env var; otherwise ordinal 0.
    """
    if device_id != -1:
        return device_id
    if task_resources and RESOURCE_NAME in task_resources:
        addresses = task_resources[RESOURCE_NAME].addresses
        if addresses:
            return int(addresses[0])
    env = os.environ if env is None else env
    if env.get(_ENV_TASK_DEVICE):
        return int(env[_ENV_TASK_DEVICE])
    return 0


def tree_group_budget_bytes(local_est=None) -> int:
    """Tree-group memory budget shared by the LOCAL vmapped forest fit
    and the statistics-plane tree groups: the estimator's
    ``maxMemoryInMB`` (Spark's aggregation-memory knob, default 256 on
    the estimators; 64MB bare default), overridable by
    SPARK_RAPIDS_ML_TPU_TREE_GROUP_BYTES. Parsed lazily at fit time so
    a malformed env value fails the FIT with a clear message."""
    import os

    raw = os.environ.get("SPARK_RAPIDS_ML_TPU_TREE_GROUP_BYTES")
    if raw is not None:
        try:
            value = int(raw)
            if value < 1:
                raise ValueError
            return value
        except ValueError:
            raise ValueError(
                f"SPARK_RAPIDS_ML_TPU_TREE_GROUP_BYTES={raw!r}: expected "
                "a positive integer byte count"
            ) from None
    if local_est is not None and local_est.has_param("maxMemoryInMB"):
        return int(local_est.get_or_default("maxMemoryInMB")) * 1024 * 1024
    return 64 * 1024 * 1024
