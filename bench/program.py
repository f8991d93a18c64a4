"""How the benchmark calls the system under test: a configuration file
turned into the program's chart, ``ICR`` and posterior server. Nothing
else of the program is used by the benchmark."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def chart(cfg: dict):
    from repro.core import charts

    args = dict(cfg["args"])
    if isinstance(args["shape0"], list):
        args["shape0"] = tuple(args["shape0"])
    return getattr(charts, cfg["chart"])(**args)


def model(cfg: dict):
    """The configuration's ``ICR`` on the kernel path, fp32 storage."""
    from repro.core import ICR, matern32

    if cfg["kernel"] != "matern32" or cfg["dtype"] != "fp32":
        raise ValueError("configurations are matern32 at fp32")
    kern = matern32.with_defaults(rho=float(cfg["rho"]),
                                  sigma=float(cfg.get("sigma", 1.0)))
    return ICR(chart=chart(cfg), kernel=kern, jitter=float(cfg["jitter"]),
               use_pallas=True)


def normals(key, shapes, scale=1.0):
    """ξ-shaped standard normals (times ``scale``), one jitted call."""

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [scale * jax.random.normal(k, s, jnp.float32)
                for k, s in zip(keys, shapes)]

    return make(key)


def posterior(cfg: dict, icr, key):
    """A served posterior made on the device from ``key``: a prior draw as
    the mean, the configuration's constant log-std."""
    from repro.core.vi import Posterior

    mean = normals(key, icr.xi_shapes())
    log_std = [jnp.full_like(m, float(cfg["posterior_log_std"]))
               for m in mean]
    return Posterior(icr=icr, mean=mean, log_std=log_std)


def server(cfg: dict, post):
    from repro.launch.serve_gp import GPFieldServer

    return GPFieldServer(post, slab=int(cfg["slab"]))


def request(req):
    from repro.launch.serve_gp import GPRequest

    return GPRequest(kind=req.kind, n=req.n, seed=req.seed)

