"""One module per kind of deployment, named by a configuration's
``driver`` key; each has ``run(...)`` returning a ``harness.Outcome``."""
