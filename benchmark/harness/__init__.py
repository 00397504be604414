"""The benchmark's own library: nothing in here is the system under test."""
