"""Plain references that decide a run's `correct`.  They import nothing
of the program under test."""
