"""Traffic kinds: each module drives one kind of load from a mix file."""
