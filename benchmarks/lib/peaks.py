"""Peak rates of ONE chip, keyed by the exact ``device_kind`` JAX reports.

A copy of ``bench.PEAKS`` (the program may change; the yardstick may not).
A device that is not in the table is an error, never a default: add it
with its source line.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (System architecture table):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1,600 Gbit/s inter-chip interconnect.  'TPU v5 lite' is what a v5e
    # chip reports as device_kind (chip run, PR 21).
    'TPU v5 lite': {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9,
                    'hbm_bytes': 16e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, too few chips, or an unknown kind."""


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise NoChip('no peak rates recorded for device_kind %r: add it to '
                     'benchmarks/lib/peaks.py with its source' % (device_kind,))
    return PEAKS[device_kind]


def require_device(chips, allow_cpu=False):
    """The device as JAX reports it: {'platform', 'kind', 'count'}.

    Raises NoChip when the platform is not a TPU, when fewer than `chips`
    devices are present, or when the kind has no row in PEAKS.
    `allow_cpu` is for the benchmark's own CPU tests at tiny sizes: it
    never reaches the command line.
    """
    import jax
    devs = jax.devices()
    dev0 = devs[0]
    device = {'platform': str(dev0.platform), 'kind': str(dev0.device_kind),
              'count': len(devs)}
    if len(devs) < int(chips):
        raise NoChip('the cell asks for %d chip(s) and JAX found %d'
                     % (chips, len(devs)))
    if allow_cpu:
        return device
    if dev0.platform != 'tpu':
        raise NoChip('JAX found no accelerator (platform %r, kind %r)'
                     % (device['platform'], device['kind']))
    peaks(device['kind'])
    return device
