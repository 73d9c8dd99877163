"""Warm-ups, one module per runner or runner set-up: a traffic file's
``warm_up`` key names one, or else its ``method`` does.  Each gives

* ``RUNNER_KEYS``: the runner keyword arguments its warm-up covers (a
  traffic file that sets any other is refused, since the window would
  compile);
* ``warm(cfg, traffic, trainer, params)``: runs every program the
  runner can dispatch in the window, at every shape the cell's traffic
  can give it, through the calls the runner makes;
* optionally ``runner_kwargs(traffic)``: the runner's keyword arguments
  built from the traffic's ``runner`` (objects such as a mesh, which a
  JSON file cannot hold); without it they are the ``runner`` as given.
"""

from __future__ import annotations


def engine(trainer, traffic: dict):
    """The engine the runner builds for the traffic's keyword arguments."""
    from repro.core.engine import make_engine
    return make_engine(trainer, use_kernel_agg=traffic.get(
        "runner", {}).get("use_kernel_agg", False))
