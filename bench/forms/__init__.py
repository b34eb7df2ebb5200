"""Integrand forms the benchmark's requests are made of, one module per
form, found by the ``form`` a configuration names.  Each module has

* ``draw(rng, request) -> params``: one request's parameters (float32
  numpy arrays with a leading function axis) from a numpy Generator;
* ``family(params, request)``: the system's ``IntegrandFamily`` for them.

The closed form of each lives in ``bench/reference/<form>.py``.
"""
