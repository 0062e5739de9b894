"""scheduler.batch_occupancy

Live slots per decode window over the runtime's slots, averaged over the
window's decode launches.
"""
META = {'name': 'scheduler.batch_occupancy', 'unit': '%', 'better': 'higher', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    w = ctx.get('windows')
    if not w:
        return None
    return 100.0 * sum(live for live, _ in w) / len(w) \
        / ctx['traffic']['slots']
