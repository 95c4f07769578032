"""By-hand timings inside the VMEM bulge chaser (one Pallas custom
call: no op inside it is on a device trace, so ``eig_chase_s`` cannot
say what its seconds are made of; ROADMAP S13, PERF.md section 6, PR 45).

    python tools/chase_probe.py shears    # microseconds a shear
    python tools/chase_probe.py hb2st     # the whole chase, three forms
    python tools/chase_probe.py shears hb2st --aot   # compile only

``shears``: a stand-alone kernel holds a [rows, 2*rows] f32 block in
VMEM and applies one form in a ``fori_loop``; a pass's time is the
slope between two trip counts (launch and loop set-up cancel). The
shear forms accumulate their block, which the ``null`` body prices.
``hb2st``: ``_hb2st_vmem_jit`` at n=8192, band 128 with the single-pass
shears, with the ladder, and with no vector sheared at all (answers
garbage, time not: the kernel has no data-dependent control flow);
the first two are compared. Run on the chip (through the builder's
tool); ``--aot`` compiles for a described v5e here and runs nothing;
``--rehearse`` runs ``hb2st`` on the CPU in interpret mode at n=300.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
AOT = "--aot" in sys.argv
REHEARSE = "--rehearse" in sys.argv
if AOT or REHEARSE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from slate_tpu.internal import band_wave_vmem as bwv

FORMS = ("null", "shear_ladder", "shear_single", "anti_ladder",
         "anti_single")
TRIPS = (1000, 5000)


def _one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _shear_kernel(form, rows, trips):
    W = 2 * rows

    def kern(v_ref, q_ref, o_ref):
        if form.startswith("anti"):
            fn = (bwv._antishear_ladder if form == "anti_ladder"
                  else bwv._antishear)
            o_ref[...] = lax.fori_loop(
                0, trips, lambda _, q: fn(q, rows, W) * 1.0001,
                q_ref[...])
            return
        # as the chaser does: the index array once, outside the loop
        lanes = bwv._shear_lanes(rows, W, rows - 1)

        def body(_, c):
            acc, v = c
            if form == "null":
                S = jnp.broadcast_to(v, (rows, W))
            elif form == "shear_ladder":
                S = bwv._shear_rowvec_ladder(v, rows - 1, rows, W)
            else:
                S = bwv._shear_rowvec(v, rows - 1, rows, W, lanes)
            return acc + S, v * 1.0001
        o_ref[...] = lax.fori_loop(0, trips, body,
                                   (q_ref[...], v_ref[...]))[0]

    def f(v, q):
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((rows, W), jnp.float32),
            in_specs=[vmem, vmem], out_specs=vmem)(v, q)
    return jax.jit(f)


def shears():
    one = _one_chip() if AOT else None
    for rows in (128, 256):
        W = 2 * rows
        for form in FORMS:
            walls = {}
            for trips in TRIPS:
                fn = _shear_kernel(form, rows, trips)
                if AOT:
                    fn.lower(*(jax.ShapeDtypeStruct(s, jnp.float32,
                                                    sharding=one)
                               for s in ((1, W), (rows, W)))).compile()
                    walls[trips] = "compiles"
                    continue
                key = jax.random.PRNGKey(rows)
                v = jnp.zeros((1, W), jnp.float32).at[0, :rows].set(
                    jax.random.normal(key, (rows,)))
                q = jax.random.normal(key, (rows, W), jnp.float32)
                fn(v, q).block_until_ready()
                best = float("inf")
                for _ in range(7):
                    t = time.perf_counter()
                    fn(v, q).block_until_ready()
                    best = min(best, time.perf_counter() - t)
                walls[trips] = best
            line = {"rows": rows, "form": form, "walls_s": walls}
            if not AOT:
                line["us_a_pass"] = ((walls[TRIPS[1]] - walls[TRIPS[0]])
                                     / (TRIPS[1] - TRIPS[0]) * 1e6)
            print(json.dumps(line), flush=True)


def _chaser(tag):
    # a function of its own a form: jit's trace cache is keyed on it
    def chaser(ab, band, n, interpret=False):
        return bwv._hb2st_vmem_jit.__wrapped__(ab, band, n, interpret)
    chaser.__name__ = f"_hb2st_{tag}"
    return jax.jit(chaser, static_argnames=("band", "n", "interpret"))


def hb2st():
    n, band = (300 if REHEARSE else 8192), 128
    rng = np.random.default_rng(4500001)
    ab = rng.standard_normal((band + 1, n)).astype(np.float32)
    for d in range(band + 1):
        ab[d, n - d:] = 0
    real = bwv.shear_form, bwv._shear_rowvec, bwv._antishear
    out, best = {}, {}
    for tag in ("single_pass", "ladder", "no_shear"):
        if tag == "ladder":
            bwv.shear_form = lambda *a, **k: "ladder"
        elif tag == "no_shear":
            bwv._shear_rowvec = (lambda v, col0, rows, W4, lanes=None:
                                 jnp.broadcast_to(v, (rows, W4)))
            bwv._antishear = lambda Q, rows, W4: Q
        fn = _chaser(tag)
        if AOT:
            fn.lower(jax.ShapeDtypeStruct((band + 1, n), jnp.float32,
                                          sharding=_one_chip()),
                     band=band, n=n).compile()
            print(json.dumps({"form": tag, "compiles": True}), flush=True)
        else:
            walls = []
            for _ in range(4):
                t = time.perf_counter()
                out[tag] = jax.block_until_ready(
                    fn(jnp.asarray(ab), band=band, n=n,
                       interpret=REHEARSE))
                walls.append(time.perf_counter() - t)
            best[tag] = min(walls[1:])
            print(json.dumps({"form": tag, "first_call_s": walls[0],
                              "walls_s": walls[1:]}), flush=True)
        bwv.shear_form, bwv._shear_rowvec, bwv._antishear = real
    if not AOT:
        print(json.dumps({
            "hb2st_s": best,
            "single_pass_against_ladder_max_abs_diff_and_scale": {
                k: [float(jnp.max(jnp.abs(a - b))),
                    float(jnp.max(jnp.abs(b)))]
                for k, a, b in zip(("d", "e", "V", "tau"),
                                   out["single_pass"], out["ladder"])}}),
              flush=True)


if __name__ == "__main__":
    for what in sys.argv[1:]:
        if what in ("shears", "hb2st"):
            globals()[what]()
