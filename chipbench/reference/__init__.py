"""Plain references that decide ``correct``. They import nothing of the
program under test and take nothing it made."""
