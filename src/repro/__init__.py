"""repro — Wolfrath & Chandra (2022) edge-sampled dependent-stream
transmission, reproduced and scaled to a fleet of edge sites served from
one cloud on TPU chips (the window step under ``lax.scan``, sharded over
the site axis).  Experiments run through the Scenario API (``repro.api``):
registry-backed components, declarative ``ScenarioConfig``, one
``Experiment`` runtime.  See README.md and docs/api.md."""

__version__ = "0.2.0"
