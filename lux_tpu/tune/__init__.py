"""Profile-guided auto-tuner: close the measurement -> knob loop.

The repo measures everything (engobs phase splits, exchange-ledger
useful_ratio, profile.v1 realized overlap, run-ledger config cohorts)
but a human still picked ``LUX_EXCHANGE`` and the GAS hysteresis by
hand. This package closes the loop per
(graph fingerprint, program, engine kind, mesh shape, device kind):

- :mod:`space` declares the knob axes the tuner may turn
  (:data:`TUNER_MANAGED`) and enumerates candidates deterministically.
- :mod:`probe` builds an executor under a candidate flag overlay
  (:func:`lux_tpu.utils.flags.overrides`) and scores a short
  fixed-iteration burst from the engobs phase split — never wall-clock
  alone.
- :mod:`search` runs successive halving over the space and returns a
  ``tuneconf.v1`` artifact; every probe and the selection land in the
  run ledger so lux_doctor attributes tuned-vs-default deltas.
- :mod:`artifact` persists/loads the artifact JSON; :mod:`cache` is the
  ShardPlanCache-shaped LRU serving warmup consults, evicted with the
  plan cache on snapshot swaps.

``tools/luxlint.py --tune`` (analysis/tuneck.py, LUX5xx) verifies the
artifacts offline — the config JSON is gated evidence, like plans.
"""

from lux_tpu.tune.artifact import (SCHEMA, key_string, list_artifacts,
                                   load, load_path, make_key, save)
from lux_tpu.tune.cache import TuneCache, tune_cache
from lux_tpu.tune.probe import ProbeResult, run_probe
from lux_tpu.tune.search import tune
from lux_tpu.tune.space import (TUNER_MANAGED, default_candidate,
                                knob_space)

__all__ = [
    "SCHEMA", "TUNER_MANAGED", "TuneCache", "ProbeResult",
    "default_candidate", "key_string", "knob_space", "list_artifacts",
    "load", "load_path", "make_key", "run_probe", "save", "tune",
    "tune_cache",
]
