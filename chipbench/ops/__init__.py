"""Timed operations, one module per operation, named by a traffic file's ``op``.

Each module exposes

* ``setup(data, traffic, devices, clock) -> common.Setup``: the program's
  set-up through its public entry points, with ``clock("from_coo")`` and
  ``clock("pack")`` around its stages;
* ``inputs(data, traffic, rng) -> list[np.ndarray]``: the pool of inputs
  the window cycles through;
* ``reference(data, traffic, inp) -> np.ndarray``: the plain float64
  answer, computed with numpy and scipy only;
* ``control(data, traffic, inp) -> np.ndarray``: the reference computed
  in the next precision below the configuration's, with ``jax.numpy`` on
  the default device (never run by the benchmark's own runs);
* ``check(out, ref) -> dict[str, float]``: the numbers compared with the
  cell's limits;
* ``floor(data, traffic) -> (bytes, flops)``: the floor work of one
  operation (``chipbench.floor``).
"""
