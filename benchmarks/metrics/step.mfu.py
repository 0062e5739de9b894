"""step.mfu

Model FLOP/s utilization: the operations the forward and backward passes
need per item times train_rate, over the chip's bf16 peak (lib/peaks.py).
The count is `train_flops_per_item(config, traffic)` of the
configuration's own builds/<config>.py where it brings one, else
lib/flops.py's for the two families the train runner falls back on.
"""
from lib import flops, stats

META = {'name': 'step.mfu', 'unit': '%', 'better': 'higher', 'source': 'host_clock',
        'layer': 'step program (XLA)',
        'moves': 'train_rate'}


def flops_per_item(config, traffic, build=None):
    if hasattr(build, 'train_flops_per_item'):
        return build.train_flops_per_item(config, traffic)
    if config.get('model') == 'transformer':
        return flops.transformer_train_flops_per_token(
            config['n_layer'], config['d_model'], config['d_inner'],
            config['vocab'], traffic['seq'])
    if config.get('model') == 'resnet':
        return flops.resnet_train_flops_per_image(
            config['depth'], traffic['side'], config['classes'])
    return None


def read(ctx):
    if 'segments' not in ctx or not ctx.get('peaks'):
        return None
    per_item = flops_per_item(ctx['config'], ctx['traffic'],
                              ctx.get('build'))
    if per_item is None:
        return None
    rate = stats.segment_rate(ctx['segments'], ctx['items_per_segment'])[0]
    return 100.0 * per_item * rate / ctx['chips'] / ctx['peaks']['bf16_flops']
