def read(run):
    acks = [w['t_ack'] - w['t_send'] for w in run['writes']
            if w['status'] == 200]
    return 1e3 * sum(acks) / len(acks) if acks else None
