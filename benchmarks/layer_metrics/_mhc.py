"""What a residual path of `hc_mult` streams (manifold-constrained
hyper-connections, arXiv:2512.24880) costs at the least, and the names its
launches have on the device trace. Data and arithmetic for the `mhc_*` metric
files beside it; everything is computed from the configuration file's keys
(`hc_mult`, `hidden_size`, `num_hidden_layers`) and the counters the program's
step samples carry (`mhc_rows`: the step's real tokens, each of which passes
every application; `mhc_apps`: applications a forward pass, 2 x layers + 1; a
program without them gives the readers nothing to read).

The kernels (Mosaic custom calls under their Pallas functions' names), an
application of the connection a sublayer and one before the head:

  `mhc_mix_in_pallas` — the flattened norm, the product with Phi, the
      mappings (the Sinkhorn iterations) and the weighted sum a sublayer
      reads: `mhc_apps` launches a forward pass (the read-out is the same
      launch with H_post and H_res compiled out).
  `mhc_mix_out_pallas` — H_res over the streams plus H_post times the
      sublayer's result, in place: `mhc_apps` - 1 a pass.

The roofline counts THE LEAST ANY IMPLEMENTATION OF THE SAME MATHEMATICS
NEEDS, never a kernel's own tiling, so that a later kernel (one that fuses a
sublayer's write-back with the next one's read) is read against the same work
and nothing reads over 100: ONE pass over the streams an application around a
sublayer — the n streams in and out, the sublayer's result in and its input
out, (2 n + 2) x hidden x 2 B a token — plus every application's Phi once a
forward pass (float32, as the configuration file states), at the HBM peak; or
the product's 2 x n hidden x (2 n + n^2) FLOPs a token an application at the
bf16 peak; the larger of the two times. The read-out's streams are NOT
counted (it needs the sampled rows alone): the least stays a least.
"""
import re

KERNEL = re.compile(r"mhc_mix_\w*pallas")
FIELDS = ("mhc_rows", "mhc_apps")
STREAM_BYTES = 2  # bf16 streams, as the configuration file states
PHI_BYTES = 4  # float32 Phi


def has_counters(samples) -> bool:
    return bool(samples) and all(f in s for s in samples for f in FIELDS)


def sizes(cfg: dict):
    """(n, hidden) of the configuration file; None without `hc_mult` > 1."""
    n = int(cfg.get("hc_mult") or 0)
    return (n, int(cfg["hidden_size"])) if n > 1 else None


def time_and_launches(trace: dict) -> tuple:
    t = sum(s for name, s in trace["op_self_s"].items() if KERNEL.search(name))
    c = sum(k for name, k in trace["op_count"].items() if KERNEL.search(name))
    return t, c


def launches_a_pass(apps: float) -> float:
    """Both kernels' launches a forward pass: a mix-in an application, a
    mix-out an application around a sublayer."""
    return 2 * apps - 1


def least_seconds(cfg: dict, rows: float, apps: float, passes: float,
                  peaks: dict) -> tuple:
    """(seconds the chip needs at the least, which peak bounds it) for
    `passes` forward passes of `rows` tokens each through `apps`
    applications (the last of them the read-out)."""
    n, c = sizes(cfg)
    maps = 2 * n + n * n
    around = apps - 1  # applications around a sublayer
    by_bytes = passes * (
        rows * around * (2 * n + 2) * c * STREAM_BYTES
        + (around * maps + n) * n * c * PHI_BYTES) / peaks["hbm_bytes_per_s"]
    by_flops = passes * rows * around * 2 * n * c * maps / peaks["flops_bf16"]
    return max(by_bytes, by_flops), ("hbm" if by_bytes >= by_flops
                                     else "flops")
