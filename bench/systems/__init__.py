"""Systems under test: each module builds one program entry from a
configuration file."""
