"""Faults planted under the mesh cell's timed path, to show that its check
sees each one: with the fault in place a run's ``correct`` has to come
out false.

    python3 bench/faults_mesh4.py --workload graph500_s26_mesh4.device_steps \
        --fault level_shift --seeds 11 12 13 --seconds 10

runs the cell (as ``run.py`` does, on the chip, at the cell's own size)
with the fault planted, once per seed, and prints the numbers compared
beside their limits.  ``bench/tests/test_faults_mesh4.py`` plants the
same faults at a size the CPU holds.  The benchmark's own runs never run
this.

* ``level_shift``: each device's block pairs src level ``ell + k`` with
  dst level ``ell`` (k = log2 of the device count) and draws its last k
  dst levels and its k dst prefix levels from their marginals;
* ``marginal_prefix``: the k dst prefix levels are drawn from their
  marginal, P(dst bit = 0) = a + c, not conditioned on the device's src
  bits;
* ``shared_key``: every device of a step draws from the same seed.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.faults import _patched  # noqa: E402


def level_shift():
    from repro.core import distributed_gen as dg

    def make(_orig):
        def shifted(get_u, thetas, didx, n, m, k, zeros, dtype):
            src, dst = dg.descend(
                get_u, lambda ell: (thetas[ell, 0], thetas[ell, 1],
                                    thetas[ell, 2]),
                n - k, m, zeros)
            return (dg.combine_ids_device(src, n - k, dtype, prefix=didx),
                    dg.combine_ids_device(dst, m, dtype))
        return shifted
    return _patched(dg, "device_block", make)


def marginal_prefix():
    import jax.numpy as jnp

    from repro.core import distributed_gen as dg

    def make(orig):
        def marginal(get_u, thetas, didx, n, m, k, zeros, dtype):
            src, dst = orig(get_u, thetas, didx, n, m, k, zeros, dtype)
            pre = jnp.zeros_like(dst)
            for ell in range(k):
                db = get_u(ell) >= thetas[ell, 0] + thetas[ell, 2]
                pre = pre * 2 + db.astype(dst.dtype)
            low = m - k
            return src, (dst & ((1 << low) - 1)) | (pre << low)
        return marginal
    return _patched(dg, "device_block", make)


def shared_key():
    import numpy as np

    from repro.core import distributed_gen as dg

    def make(orig):
        def same(base_seed, step, n_dev):
            return np.full(n_dev, orig(base_seed, step, n_dev)[0])
        return same
    return _patched(dg, "step_seeds", make)


FAULTS = {f.__name__: f for f in (level_shift, marginal_prefix, shared_key)}


def main(argv=None) -> int:
    """``bench/faults.py``'s runner, choosing among these faults."""
    from bench import faults
    faults.FAULTS = FAULTS
    return faults.main(argv)


if __name__ == "__main__":
    sys.exit(main())
