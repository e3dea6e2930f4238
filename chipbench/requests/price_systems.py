"""price_systems: a ``PriceSystemsRequest`` of one raw ``spec()`` group.

params: ``groups``, a list of groups (each a list of spec dicts); each
request carries one, drawn uniformly.  NRE amortizes within the group.
Compared: system names and each system's RE, NRE and total per unit.
"""
import numpy as np

from harness.reference import spec_system
from repro.service import PriceSystemsRequest


def shape(params, rng):
    return {"group": int(rng.integers(len(params["groups"])))}


def make(space_size, params, shape, rng):
    return PriceSystemsRequest(specs=tuple(
        dict(d) for d in params["groups"][shape["group"]]))


def rows(req):
    return len(req.specs)


def payload(req, resp, pick):
    rows = resp.result.rows
    return {"names": [r["system"] for r in rows],
            "total": np.asarray([r["total"] for r in rows]),
            "re": np.asarray([r["re_total"] for r in rows]),
            "nre": np.asarray([r["nre_total"] for r in rows])}


def answer(req, ref, pick):
    systems = [spec_system(d) for d in req.specs]
    g = ref.group(systems)
    return {"names": [s["name"] for s in systems],
            **{k: np.asarray([float(x) for x in g[k]])
               for k in ("total", "re", "nre")}}


def compare(req, ref, got, pick):
    want = answer(req, ref, pick)
    return [("exact", got["names"], want["names"])] + [
        ("rel", got[k], want[k]) for k in ("total", "re", "nre")]
