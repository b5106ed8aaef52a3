"""Seeded schema-coherence violations: ``queue_summary`` emits an
unknown key and drops a required one; ``recovery_summary`` drops a
required key."""


def queue_summary():
    return {
        "depth": 1,
        "producer_wait_s": 0.0,
        "consumer_wait_s": 0.0,
        "bogus_key": 9,
    }


def recovery_summary():
    return {
        "recovered_jobs": 0,
        "requeued_jobs": 0,
        "served_from_spool": 0,
        "spool_corrupt": 0,
        "journal_replayed": 0,
        "journal_records": 0,
        "journal_compactions": 0,
        "slot_restarts": 0,
    }
