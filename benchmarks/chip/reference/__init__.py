"""Plain reference for the store's answers: a replay of the event log
and numpy analytics over the snapshots it gives.  Imports nothing of
the store under test."""
