"""ssm_scan_roofline: the selective scan's share (%) of its HBM roofline:
the bytes the step's scans must move (`chipbench/ssm_scan_bytes.py`, from
shapes) over the device time per step under the program's `ssm_scan`
scope (the scan core, forward, recompute and backward, jnp or Pallas) and
the chips' HBM bandwidth (`peaks.json`). None where the program has no
`ssm_scan` scope."""
import json
import os

from chipbench import scopes, ssm_scan_bytes


def hbm_bytes_per_s(kind):
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r}")
    return float(peaks[kind]["hbm_bytes_per_s"])


def read(ctx):
    ms = scopes.scope_ms(ctx, "ssm_scan")
    if ms is None:
        return None
    return 100.0 * ssm_scan_bytes.step_bytes(ctx.cfg, ctx.mix) / (
        ms / 1000.0) / (ctx.chips * hbm_bytes_per_s(ctx.device_kind))
