"""Plain references of the configurations, copied from the system
under test so that no change to the program moves them."""
