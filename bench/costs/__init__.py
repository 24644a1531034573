"""Operations and bytes of the work that metrics divide by time."""
