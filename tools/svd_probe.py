"""By-hand stage timings of the two-stage SVD with both sets of vectors
(PERF.md section 6, PR 48): every stage of ``slate.gesvd(MethodSVD
.TwoStage)`` called on its own and drained, at the cell's shape, in one
process, solo, then whole public calls and the plain residuals.

    python tools/svd_probe.py                 # m=12288 n=8192 nb=256
    python tools/svd_probe.py --host-bdsqr 180   # and the host bdsqr,
                                # in a child with a watchdog, last
    python tools/svd_probe.py --rehearse      # CPU, m=384 n=256 nb=64

Run on the chip (through the builder's tool). One JSON line a stage on
stdout, and all of them in ``chiprun_out/svd_probe.json``.
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
REHEARSE = "--rehearse" in sys.argv
if REHEARSE:
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp
import numpy as np

import slate_tpu as slate
from slate_tpu.linalg import bulge, ge2tb as g2
from slate_tpu.robust import ladder
from slate_tpu.types import MethodSVD, Op, Option

M, N, NB = (384, 256, 64) if REHEARSE else (12288, 8192, 256)
REPS = 1 if REHEARSE else 3
OUT = os.path.join(ROOT, "chiprun_out")
LINES = []


def say(**line):
    LINES.append(line)
    print(json.dumps(line), flush=True)


def drained(x):
    jax.block_until_ready(jax.tree_util.tree_leaves(x))
    return x


def timed(name, fn, reps=REPS, **more):
    """First call (compiles), then the fastest and the median of
    ``reps`` drained calls."""
    t0 = time.perf_counter()
    out = drained(fn())
    first = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = drained(fn())
        walls.append(time.perf_counter() - t0)
    say(stage=name, first_s=first, best_s=min(walls),
        median_s=float(np.median(walls)), **more)
    return out


def peak_gib():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def host_bdsqr(limit_s):
    """``bulge.bdsqr`` with vectors on the saved (d, e) in a child that
    never touches the chip, stopped at ``limit_s`` seconds or 24 GiB."""
    code = ("import time, numpy as np, resource\n"
            "from slate_tpu.linalg.bulge import bdsqr\n"
            "z = np.load(r'%s')\n"
            "t0 = time.perf_counter()\n"
            "s, U, VT = bdsqr(z['d'], z['e'], want_uv=True)\n"
            "print(time.perf_counter() - t0, "
            "resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20)\n"
            % os.path.join(OUT, "svd_probe_de.npz"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    t0 = time.perf_counter()
    child = subprocess.Popen([sys.executable, "-c", code], env=env,
                             stdout=subprocess.PIPE, text=True)
    rss = 0.0
    while child.poll() is None:
        time.sleep(1.0)
        try:
            with open(f"/proc/{child.pid}/status") as f:
                kb = [int(x.split()[1]) for x in f
                      if x.startswith("VmRSS")]
            rss = max(rss, kb[0] / 2 ** 20 if kb else 0.0)
        except OSError:
            pass
        if time.perf_counter() - t0 > limit_s or rss > 24:
            child.kill()
            say(stage="bdsqr_host", stopped_after_s=time.perf_counter() - t0,
                rss_gib=rss, limit_s=limit_s)
            return
    said = child.stdout.read().split()
    say(stage="bdsqr_host", wall_s=float(said[0]) if said else None,
        maxrss_gib=float(said[1]) if len(said) > 1 else rss,
        rc=child.returncode)


def main():
    os.makedirs(OUT, exist_ok=True)
    dev = jax.devices()[0]
    say(stage="device", platform=dev.platform, kind=dev.device_kind,
        m=M, n=N, nb=NB, jax=jax.__version__)
    grid = slate.Grid(1, 1, devices=[dev])
    A0 = slate.random_matrix(M, N, NB, grid, jnp.float32, seed=48)
    drained(A0.data)
    opts = {Option.MethodSVD: MethodSVD.TwoStage,
            Option.TrailingPrecision: "bf16_6x"}

    from slate_tpu.internal.band_wave_vmem import preferred_eig_band
    band = preferred_eig_band(N, A0.dtype)
    A = timed("retile", lambda: A0.retile(band) if band < NB else A0,
              band=band)
    Aout, Tq, Tl = timed("ge2tb", lambda: g2.ge2tb(A, opts))
    ub = timed("gather", lambda: g2.ge2tb_gather(Aout))
    out = timed("tb2bd", lambda: g2.tb2bd(ub))
    d, e, Vu, tauu, Vv, tauv, phase0 = out
    say(stage="tb2bd.rung", rung=ladder.tb2bd_ladder().last_rung,
        demotions=[str(x) for x in ladder.demotion_log()])
    np.savez(os.path.join(OUT, "svd_probe_de.npz"), d=d, e=e)
    if not REHEARSE and "--wave" in sys.argv:
        os.environ["SLATE_TB2BD"] = "wave"
        dw = timed("tb2bd.wave", lambda: g2.tb2bd(ub), reps=1)
        del os.environ["SLATE_TB2BD"]
        say(stage="tb2bd.wave_vs_vmem",
            d_max=float(np.abs(np.abs(dw[0]) - np.abs(d)).max()))
        del dw

    s, Ub, Vb = timed("bdsdc", lambda: bulge.bdsdc(d, e, grid,
                                                   np.float32))
    say(stage="bdsdc.values", s_max=float(s[0]), s_min=float(s[-1]),
        peak_gib=peak_gib())
    u2 = timed("unmbr_tb2bd.u", lambda: bulge.apply_bulge_reflectors(
        Vu, tauu, Ub, A.nb, grid=grid))
    v2 = timed("unmbr_tb2bd.v", lambda: bulge.apply_bulge_reflectors(
        Vv, tauv, Vb, A.nb, grid=grid))
    del Ub, Vb
    Ubm = timed("embed.u", lambda: slate.Matrix.from_dense(
        g2._rows_padded_jit(u2, rows=M), nb=A.nb, grid=grid))
    del u2
    U = timed("unmbr_ge2tb.u", lambda: g2.unmbr_ge2tb_u(
        Op.NoTrans, Aout, Tq, Ubm, opts))
    del Ubm, U
    Vbm = timed("embed.v", lambda: slate.Matrix.from_dense(
        v2, nb=A.nb, grid=grid))
    del v2
    Vm = timed("unmbr_ge2tb.v", lambda: g2.unmbr_ge2tb_v(
        Op.NoTrans, Aout, Tl, Vbm, opts))
    del Vbm
    VT = timed("transpose.v",
               lambda: slate.conj_transpose(Vm).materialize())
    del Vm, VT, Aout, out, Vu, Vv, A
    say(stage="stages_done", peak_gib=peak_gib())

    # ------------------------------------------------ whole public calls
    walls = []
    for i in range(2 if REHEARSE else 5):
        t0 = time.perf_counter()
        s, U, VT = slate.gesvd(A0, opts, want_u=True, want_vt=True)
        drained((U.data, VT.data))
        walls.append(time.perf_counter() - t0)
    say(stage="slate.gesvd", walls_s=walls, peak_gib=peak_gib())

    # ------------------------------------------------ the plain residuals
    from benchmarks.harness import plain_svd
    Ad = A0.to_dense()
    numbers = plain_svd.equations(Ad, s, U.to_dense(), VT.to_dense())
    say(stage="equations", in_eps={k: v / 2.0 ** -24
                                   for k, v in numbers.items()},
        descending=plain_svd.descending(s))
    t0 = time.perf_counter()
    ref = plain_svd.reference_values(Ad)
    say(stage="reference", seconds=time.perf_counter() - t0,
        values_max_eps=plain_svd.values_error(s, ref) / 2.0 ** -24,
        s_ref_max=float(ref[0]), s_ref_min=float(ref[-1]))
    for flag in sys.argv:
        if flag == "--host-bdsqr":
            host_bdsqr(float(sys.argv[sys.argv.index(flag) + 1]))
    with open(os.path.join(OUT, "svd_probe.json"), "w") as f:
        json.dump(LINES, f, indent=1)


if __name__ == "__main__":
    main()
