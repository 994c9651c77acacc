def read(run):
    """% of the operations a closed loop was dealt that the client began.
    At 100 the deck ran out before the window did and ``evals_per_s`` reads
    the deck's depth (``max_rate_per_s``), not the server's rate."""
    client = run.get("client") or {}
    dealt = client.get("scheduled")
    if run.get("loop") != "closed" or not dealt:
        return None
    return 100.0 * len(client["records"]) / dealt
