"""The benchmark's own library: everything a run needs besides the system
under test. Nothing here is imported by the program."""
